"""Run a benchmark loop at a small size on the CPU, past the harness's look
for a chip (the rest of the run is the benchmark's own path)."""
import jax

from chipbench import harness, traffic

#: cell -> (configuration, traffic mix, end-to-end metrics and units)
CELLS = {
    "study.batch": ("snds_paper_study_1chip", "study_closed",
                    {"study_events_per_s": "events/s"}),
}


def run(workload: str, n_patients: int, seconds: float, seed: int,
        **mix_overrides):
    config, mix_name, metrics = CELLS[workload]
    cell = {"name": workload, "config": config, "traffic": mix_name,
            "chips": 1}
    bench = {"end_to_end": [{"name": m, "unit": u} for m, u in
                            dict(metrics, setup_s="s").items()],
             "per_layer": []}
    cfg = harness.load_config(config)
    cfg["n_patients"] = n_patients
    mix = dict(traffic.load(mix_name), **mix_overrides)
    return harness.run_cell(bench, cell, seed, seconds, False,
                            jax.devices()[:1], cfg=cfg, mix=mix)
