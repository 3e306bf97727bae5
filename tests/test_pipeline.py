"""GPipe pipeline parallelism: pipelined == sequential (fwd + grads)."""
import json
import os
import subprocess
import sys
import textwrap


SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_gpipe_matches_sequential():
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_transformer

        P_STAGES, LPS, M, MB, D = 4, 2, 8, 4, 16
        mesh = jax.make_mesh((P_STAGES,), ("pipe",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        key = jax.random.key(0)
        Ws = jax.random.normal(key, (P_STAGES, LPS, D, D), jnp.float32) * 0.1

        def layer(W, x):
            return jnp.tanh(x @ W)

        mbs = jax.random.normal(jax.random.key(1), (M, MB, D), jnp.float32)

        # sequential reference
        ref = mbs
        for s in range(P_STAGES):
            for l in range(LPS):
                ref = jax.vmap(lambda x: layer(Ws[s, l], x))(ref)

        piped = pipeline_transformer(layer, mesh, P_STAGES)(Ws, mbs)
        err = float(jnp.abs(piped - ref).max())

        # grads through the pipeline
        def loss_piped(Ws):
            return pipeline_transformer(layer, mesh, P_STAGES)(Ws, mbs).sum()
        def loss_ref(Ws):
            y = mbs
            for s in range(P_STAGES):
                for l in range(LPS):
                    y = jnp.tanh(y @ Ws[s, l])
            return y.sum()
        g1 = jax.grad(loss_piped)(Ws)
        g2 = jax.grad(loss_ref)(Ws)
        gerr = float(jnp.abs(g1 - g2).max())
        print(json.dumps({"fwd_err": err, "grad_err": gerr}))
    """)
    r = run_subprocess(code)
    assert r["fwd_err"] < 1e-5, r
    assert r["grad_err"] < 1e-4, r
