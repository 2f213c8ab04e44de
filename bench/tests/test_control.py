"""The control must fail the check (run on a TPU machine:
``python -m pytest bench/tests/test_control.py``; it skips elsewhere,
since on the CPU every matmul precision is float32).

The control is the reference in the program's place, computed one
precision below the configuration's float32-at-HIGHEST: "high", three
bfloat16 passes.  At each configuration's own sizes and sample, on
three seeds, its largest logit gap to the "highest" reference must
exceed the configuration's limit: otherwise the check could not see a
program that dropped to that precision."""
import jax
import pytest

from bench import control, run


@pytest.fixture(scope="module")
def tpu():
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control needs a TPU")


@pytest.mark.parametrize("config", ["qrmark-256-t64", "sequential-256"])
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_fails_the_check(tpu, config, seed):
    cfg = run._json(run.BENCH / "configs" / f"{config}.json")
    traffic = run._json(run.BENCH / "traffic" / "offline-stream-b32.json")
    got = control.readings(cfg, traffic, seed)
    assert got["max_logit_diff"] > cfg["check"]["max_logit_diff"], got
