"""Device programs carry stable names: a trace (or an HLO dump) names
the fused-DAG kernel, the serving step, the prefill and the
certification reference by what they are, never ``jit_wrapped`` or
``jit__lambda_``.  The names are read back from the ``hlo_module`` of
every operation in a CPU profiler trace."""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import pipeline as plmod
from repro.launch import serve
from repro.patterns.analytics import tpchq6_pipeline

ANONYMOUS = {"jit_wrapped", "jit__lambda_", "jit__lambda"}


def _modules(log_dir, run):
    """HLO module names of every operation the CPU ran in ``run()``."""
    with jax.profiler.trace(str(log_dir)):
        run()
    [path] = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                module = dict(ev.stats).get("hlo_module")
                if module:
                    names.add(module)
    return names


def _q6():
    rows = 1 << 12
    call = plmod.lower_pipeline(tpchq6_pipeline(rows)[0], fused=True)
    rng = np.random.default_rng(0)
    cols = {n: rng.random(rows, np.float32) for n in ("qty", "price",
                                                      "disc")}
    float(np.asarray(call(**cols)))


def _serve():
    serve.serve_continuous("granite-3-2b", True, 2, 2, prompt_lens=(3, 5))


@pytest.mark.parametrize("run, want", [
    (_q6, {"jit_fused_dag_tpchq6"}),
    (_serve, {"jit_serve_step", "jit_serve_prefill",
              "jit_certify_reference"}),
], ids=["fused_dag", "serving"])
def test_device_programs_carry_their_names(tmp_path, run, want):
    names = _modules(tmp_path, run)
    assert want <= names, sorted(names)
    assert not names & ANONYMOUS, sorted(names)
