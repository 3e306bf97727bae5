"""The closed study loop under the module name that ``tests/test_tracing.py``
imports it by: ``StudyCell(cfg, mix, seed)`` is ``loops/closed_study.py``'s
``Cell`` on JAX's first device."""
import jax

from chipbench import harness


def StudyCell(cfg, mix, seed):
    return harness._load_path("loops", "closed_study").Cell(
        cfg, mix, seed, jax.devices()[:1])
