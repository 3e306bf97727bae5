"""The command refuses to measure anywhere but on a TPU, and the
benchmark file names what the harness finds by name."""
import json
import os
import subprocess
import sys

from chipbench import harness

ROOT = os.path.dirname(harness.HERE)


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "study.batch", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_every_name_resolves_to_a_file():
    bench = harness.load_benchmark(ROOT)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert harness.load_config(c["name"])["name"] == c["name"]
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(harness.HERE, "traffic",
                                           w["traffic"] + ".json"))
        assert w["config"] in {c["name"] for c in bench["configs"]}
    for m in bench["per_layer"]:
        assert callable(harness._load_path("metrics", m["name"]).read)
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert len(json.dumps(bench)) < 64 * 1024
