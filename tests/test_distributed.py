"""Distributed tests: shard_map flattening + feature drivers on a forced
multi-device CPU mesh (subprocess — the main process must keep 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_distributed_flatten_matches_local():
    code = textwrap.dedent("""
        import json
        import jax, numpy as np
        from repro.data.synthetic import SyntheticConfig, generate_dcir
        from repro.core.flattening import flatten_star, distributed_flatten
        from repro.core.schema import DCIR_SCHEMA

        cfg = SyntheticConfig(n_patients=200, seed=3)
        dcir = generate_dcir(cfg)
        flat, _ = flatten_star(DCIR_SCHEMA, dcir)
        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        dflat, ovf = distributed_flatten(DCIR_SCHEMA, dcir, mesh)
        a = flat.to_numpy(); b = dflat.to_numpy()
        print(json.dumps({
            "local_rows": int(flat.count), "dist_rows": int(dflat.count),
            "overflow": int(ovf),
            "key_sum_local": int(np.sort(a["flow_id"]).sum()),
            "key_sum_dist": int(np.sort(b["flow_id"]).sum()),
            "pid_sum_local": int(a["patient_id"].sum()),
            "pid_sum_dist": int(b["patient_id"].sum()),
        }))
    """)
    r = run_subprocess(code)
    assert r["overflow"] == 0
    assert r["local_rows"] == r["dist_rows"]
    assert r["key_sum_local"] == r["key_sum_dist"]
    assert r["pid_sum_local"] == r["pid_sum_dist"]


def test_exchange_partitions_by_key():
    """After exchange, every shard holds only keys that hash to it."""
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.columnar import ColumnarTable
        from repro.core.flattening import exchange

        n = 4
        mesh = jax.make_mesh((n,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        keys = np.arange(4096, dtype=np.int32)
        t = ColumnarTable.from_columns({"k": keys})

        def body(cols, valid):
            # valid arrives as the packed bitset, word-sharded on "data"
            tt = ColumnarTable.from_columns(cols, valid=valid)
            out, ovf = exchange(tt, "k", "data", n, 4096)
            me = jax.lax.axis_index("data")
            kk = out.columns["k"].astype(jnp.uint32)
            h = kk * jnp.uint32(0x9E3779B1); h = h ^ (h >> 16)
            bad = out.valid_bool() & ((h % n).astype(jnp.int32) != me)
            # rank-1 per-shard outputs (scalars cannot carry a 'data' spec)
            return bad.sum()[None], ovf[None], out.count[None]

        fn = jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data"), P("data")),
                           check_vma=False)
        bad, ovf, cnt = fn(dict(t.columns), t.valid)
        print(json.dumps({"bad": int(np.asarray(bad).sum()),
                          "overflow": int(np.asarray(ovf).sum()),
                          "total_rows": int(np.asarray(cnt).sum())}))
    """)
    r = run_subprocess(code)
    assert r["bad"] == 0
    assert r["overflow"] == 0
    assert r["total_rows"] == 4096


def test_sharded_train_step_runs():
    """Reduced model, (2 data, 2 model) mesh: one sharded train step."""
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.models import get_bundle
        from repro.train.train_step import init_train_state, make_train_step
        from repro.train.optimizer import AdamWConfig
        from repro.distributed.sharding import param_shardings, batch_shardings
        from repro.configs.base import SHAPES

        b = get_bundle("qwen2-1.5b", reduced=True)
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with jax.set_mesh(mesh):
            state = init_train_state(b, jax.random.key(0))
            p_sh = param_shardings(b.cfg, mesh, state["params"])
            state = {"params": jax.device_put(state["params"], p_sh),
                     "opt": state["opt"]}
            step = jax.jit(make_train_step(b, AdamWConfig()))
            batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 32),
                                                  3, b.cfg.vocab_size)}
            state, m = step(state, batch)
            print(json.dumps({"loss": float(m["loss"])}))
    """)
    r = run_subprocess(code)
    assert 0 < r["loss"] < 20


def test_dryrun_artifacts_if_present():
    """Integration gate: if the dry-run matrix ran, every cell must be ok."""
    out_dir = os.path.join(os.path.dirname(__file__), "..",
                           "benchmarks", "results", "dryrun")
    if not os.path.isdir(out_dir) or not os.listdir(out_dir):
        pytest.skip("dry-run matrix not generated yet")
    bad = []
    for f in os.listdir(out_dir):
        if not f.endswith(".json"):
            continue
        with open(os.path.join(out_dir, f)) as fh:
            rec = json.load(fh)
        if not (rec.get("ok") or rec.get("skipped")):
            bad.append((f, rec.get("error")))
    assert not bad, bad


def test_sharded_moe_matches_unsharded():
    """EP shard_map path == dense path numerically (same params, same batch).

    Capacity semantics differ (per-group vs global) only when tokens drop;
    the reduced config has generous capacity so outputs must match closely.
    """
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import get_bundle

        b = get_bundle("deepseek-moe-16b", reduced=True)
        params = b.init(jax.random.key(0))
        batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 32),
                                              3, b.cfg.vocab_size)}
        l_dense = float(b.train_loss(params, batch))   # no mesh: dense path
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with jax.set_mesh(mesh):
            l_ep = float(jax.jit(b.train_loss)(params, batch))
        print(json.dumps({"dense": l_dense, "ep": l_ep}))
    """)
    r = run_subprocess(code)
    assert abs(r["dense"] - r["ep"]) < 0.05, r


def test_sharded_forward_matches_unsharded_dense_arch():
    """SP constraints must not change numerics for a dense arch."""
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import get_bundle

        b = get_bundle("gemma3-12b", reduced=True)
        params = b.init(jax.random.key(0))
        batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 32),
                                              3, b.cfg.vocab_size)}
        l1 = float(b.train_loss(params, batch))
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with jax.set_mesh(mesh):
            l2 = float(jax.jit(b.train_loss)(params, batch))
        print(json.dumps({"unsharded": l1, "sharded": l2}))
    """)
    r = run_subprocess(code)
    assert abs(r["unsharded"] - r["sharded"]) < 0.02, r


def test_exposures_sharded_matches_local():
    """Patient-partitioned shard-local exposures == global exposures."""
    code = textwrap.dedent("""
        import json
        import jax, numpy as np
        from repro.core import (DCIR_SCHEMA, distributed_flatten, exposures,
                                exposures_sharded, drug_dispenses, flatten_star)
        from repro.data.synthetic import SyntheticConfig, generate_dcir

        cfg = SyntheticConfig(n_patients=300, seed=9)
        dcir = generate_dcir(cfg)
        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        # patient-partitioned flat table (the layout the launcher guarantees)
        dflat, ovf = distributed_flatten(DCIR_SCHEMA, dcir, mesh)
        drugs = drug_dispenses()(dflat, compact=False)
        sharded = exposures_sharded(drugs, cfg.n_patients, mesh,
                                    purview_days=45)

        flat, _ = flatten_star(DCIR_SCHEMA, dcir)
        ref = exposures(drug_dispenses()(flat), cfg.n_patients,
                        purview_days=45)

        a = sharded.to_numpy(); b = ref.to_numpy()
        key = lambda d: sorted(zip(d["patient_id"].tolist(),
                                   d["value"].tolist(),
                                   d["start"].tolist(), d["end"].tolist()))
        print(json.dumps({"overflow": int(ovf), "match": key(a) == key(b),
                          "n": len(key(a))}))
    """)
    r = run_subprocess(code)
    assert r["overflow"] == 0
    assert r["match"] and r["n"] > 0, r


def test_sharded_fractures_match_single_device():
    """The fractures washout loop under the patient-sharded plan path: each
    shard walks its own candidate count, and the kept (patient, site, date)
    rows and the cohorts equal the single-device study's.  (Which code a
    kept row carries, among candidates on one patient, site and date,
    follows the table's row order, which the exchanges change.)"""
    code = textwrap.dedent("""
        import json
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.core import DCIR_SCHEMA, biology_acts, medical_acts_dcir
        from repro.data.synthetic import SyntheticConfig, generate_dcir
        from repro.study import Study

        n = 300
        dcir = generate_dcir(SyntheticConfig(n_patients=n, seed=11))
        q = (Study(n_patients=n).flatten(DCIR_SCHEMA)
             .extract(medical_acts_dcir(), name="acts")
             .extract(biology_acts(), name="bio")
             .transform("fractures", "acts", "bio", name="fractures",
                        fracture_act_codes=list(range(30)),
                        fracture_diag_codes=list(range(1080, 1090)),
                        washout_days=30)
             .transform("infarctus", "bio", name="mi",
                        diag_codes=list(range(1085, 1100)), washout_days=30)
             .cohort("fractured", "fractures").cohort("mi_c", "mi"))
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
        solo = q.run(dict(dcir))
        sharded = q.run(dict(dcir), mesh=mesh)
        rows = lambda r, k: sorted(zip(*(r.events[k].to_numpy()[c].tolist()
                                         for c in ("patient_id", "group_id",
                                                   "start"))))
        print(json.dumps({
            "n": {k: len(rows(solo, k)) for k in ("fractures", "mi")},
            "same_rows": all(rows(solo, k) == rows(sharded, k)
                             for k in ("fractures", "mi")),
            "same_cohorts": all(np.array_equal(
                np.asarray(solo.cohorts[k].subjects),
                np.asarray(sharded.cohorts[k].subjects))
                for k in ("fractured", "mi_c")),
        }))
    """)
    r = run_subprocess(code)
    assert r["same_rows"] and r["same_cohorts"], r
    assert min(r["n"].values()) > 0, r


def test_sharded_service_matches_solo_on_four_devices():
    """A 4-device mesh service plans each query for its shard count: the
    exchanges stay in, so a flatten join matches rows that live on other
    devices, and the rows land on the mesh the programs read them on."""
    code = textwrap.dedent("""
        import json
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.core import DCIR_SCHEMA, drug_dispenses
        from repro.data.synthetic import SyntheticConfig, generate_dcir
        from repro.study import CohortQueryService, Study

        n = 300
        dcir = generate_dcir(SyntheticConfig(n_patients=n, seed=5))
        q = (Study(n_patients=n).flatten(DCIR_SCHEMA)
             .extract(drug_dispenses(codes=list(range(64))), name="drugs")
             .patients("IR_BEN").cohort("base", "extract_patients")
             .cohort("drugged", "drugs").cohort("final", "drugged & base"))
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
        solo = q.run(dict(dcir))
        svc = CohortQueryService(dict(dcir), mesh=mesh)
        t = svc.submit(q)
        svc.drain()
        rows = lambda r: sorted(zip(*(r.events["drugs"].to_numpy()[c]
                                      .tolist() for c in ("patient_id",
                                                          "value", "start"))))
        env = svc._env["ER_PRS"].columns["flow_id"]
        print(json.dumps({
            "status": t.status,
            "same_rows": rows(t.result) == rows(solo),
            "same_cohorts": all(np.array_equal(
                np.asarray(t.result.cohorts[k].subjects),
                np.asarray(solo.cohorts[k].subjects)) for k in solo.cohorts),
            "resident_devices": len(env.sharding.device_set),
        }))
    """)
    r = run_subprocess(code)
    assert r["status"] == "done"
    assert r["same_rows"] and r["same_cohorts"]
    assert r["resident_devices"] == 4
