"""A run with the timed path broken underneath must come out not
correct.  Each test drives the rest of a run at a tiny size on the CPU
(the harness's look for a chip switched off) with one fault planted in
the system under test, where the answer is produced:

* an answer altered: a message bit of every image, and the first
  image's ``ok``, flipped as RS hands them over;
* a logit altered: decode's output moved by 0.01;
* half of the batch left out: half of each result's rows dropped
  (offline) or every other request never answered (online).

A clean run of the same size must come out correct."""
import copy

import numpy as np
import pytest

from bench import run


def _tiny(cell_name):
    spec, cell, cfg, traffic = run.load_cell(cell_name)
    cfg = copy.deepcopy(cfg)
    cfg["detection"].update(tile=16, img_size=32, resize_src=36)
    cfg["raw_size"] = 36
    cfg["embed_rms"] = 0.25      # a 16x16 tile needs more to decode
    cfg["extractor"].update(channels=8, depth=2)
    cfg["check"]["sample_images"] = 24
    traffic = dict(traffic, pool=16)
    if traffic["kind"] == "offline":
        traffic.update(batch=8, warm_batches=2)
    else:
        traffic.update(rate_per_s=20.0, warm_requests=8, drain_s=3.0)
    return spec, cell, cfg, traffic


def _run(cell_name, seed=3):
    spec, cell, cfg, traffic = _tiny(cell_name)
    return run.run_cell(spec, cell, cfg, traffic, seed=seed, seconds=1.0,
                        trace=False, require_tpu=False)


def _flip_message(monkeypatch):
    from repro.core import stages

    orig = stages.StageRegistry.rs_correct

    def rs_correct(self, bits):
        msg, ok, ncorr = orig(self, bits)
        return msg.at[:, 0].set(1 - msg[:, 0]), ok.at[0].set(~ok[0]), ncorr

    monkeypatch.setattr(stages.StageRegistry, "rs_correct", rs_correct)


def _shift_logits(monkeypatch):
    from repro.kernels import ops

    orig = ops.fused_extractor

    def fused_extractor(*a, **kw):
        out = orig(*a, **kw)
        if isinstance(out, tuple):
            return (out[0] + 0.01,) + tuple(out[1:])
        return out + 0.01

    monkeypatch.setattr(ops, "fused_extractor", fused_extractor)


def _drop_half_offline(monkeypatch):
    from repro.core import detect

    orig = detect.DetectionPipeline._finish

    def _finish(self, *a, **kw):
        out = orig(self, *a, **kw)
        return {k: v[: max(1, len(v) // 2)] for k, v in out.items()}

    monkeypatch.setattr(detect.DetectionPipeline, "_finish", _finish)


def _drop_half_online(monkeypatch):
    from repro.serving import server

    orig = server.DetectionServer._settle

    def _settle(self, slot, result, **kw):
        if slot.rid % 2:
            return None       # never answered
        return orig(self, slot, result, **kw)

    monkeypatch.setattr(server.DetectionServer, "_settle", _settle)


@pytest.mark.parametrize("cell", ["qrmark-256-t64.offline",
                                  "qrmark-256-t64.online"])
def test_clean_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


@pytest.mark.parametrize("cell,fault,number", [
    ("qrmark-256-t64.offline", _flip_message, "rs_mismatch"),
    ("qrmark-256-t64.offline", _shift_logits, "max_logit_diff"),
    ("qrmark-256-t64.offline", _drop_half_offline, "missing"),
    ("qrmark-256-t64.online", _flip_message, "rs_mismatch"),
    ("qrmark-256-t64.online", _shift_logits, "max_logit_diff"),
    ("qrmark-256-t64.online", _drop_half_online, "missing"),
])
def test_fault_is_not_correct(monkeypatch, cell, fault, number):
    fault(monkeypatch)
    res = _run(cell)
    assert not res["correct"]
    c = res["checks"][number]
    assert c["value"] > c["limit"], res["checks"]


def test_sequential_fault_is_not_correct(monkeypatch):
    from repro.core import extractor

    orig = extractor.extractor_forward

    def shifted(params, x):
        return orig(params, x) + 0.01

    monkeypatch.setattr(extractor, "extractor_forward", shifted)
    from repro.core import stages
    monkeypatch.setattr(stages, "extractor_forward", shifted)
    res = _run("sequential-256.offline")
    assert not res["correct"]
    assert res["checks"]["max_logit_diff"]["value"] > 2e-3
    np.testing.assert_equal(res["checks"]["missing"]["value"], 0)
