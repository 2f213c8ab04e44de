"""The yardstick on the CPU: analytic counts, the trace reduction on a
small recorded trace, and the reference against the program's codec."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, reference, run, trace_reduce


def _cfg(name):
    return run._json(run.BENCH / "configs" / f"{name}.json")


def test_conv_flops_per_pixel():
    assert flops.conv_flops_per_pixel(64, 7, 60) == 514944


@pytest.mark.parametrize("name,gflop", [("qrmark-256-t64", 2.150),
                                        ("sequential-256", 33.99)])
def test_step_flops_per_image(name, gflop):
    assert flops.step_flops(_cfg(name)) / 1e9 == pytest.approx(gflop,
                                                               abs=5e-3)


def test_trace_reduction_on_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1     # the CPU's operations are host events
    spans = trace_reduce.Spans()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    spans.on = True
    with spans("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
            with spans("bench.sleep"):
                time.sleep(0.05)
    spans.on = False
    jax.profiler.stop_trace()
    tr = trace_reduce.load(str(tmp_path), spans.items)
    assert tr.devices == 1
    assert tr.window_s >= 0.15
    busy = trace_reduce.busy_s(tr)
    assert 0 < busy < tr.window_s - 0.1
    idle = trace_reduce.idle_share(tr)
    assert idle == pytest.approx(1 - busy / tr.window_s)
    assert idle > 0.5
    mod = trace_reduce.module_s(tr, "jit__lambda")
    assert mod is not None and 0 < mod <= tr.window_s
    assert trace_reduce.module_runs(tr, "jit__lambda") >= 3
    assert trace_reduce.module_s(tr, "jit__lamb") is None
    assert trace_reduce.top_ops(tr)[0][0].startswith("jit__lambda/")
    gaps = trace_reduce.idle_gaps(tr, 3)
    assert all(g[0].startswith("bench.sleep") for g in gaps)
    assert sum(g[1] for g in gaps) == pytest.approx(0.15, rel=0.3)


def test_reference_rs_agrees_with_the_program_codec():
    from repro.core.rs.codec import RSCode, rs_decode, rs_encode

    code, rs = RSCode(4, 15, 12), reference.RS(4, 15, 12)
    rng = np.random.default_rng(0)
    words = []
    for i in range(600):
        w = rs_encode(code, rng.integers(0, 2, 48))
        assert (rs.encode(w[:48]) == w).all()
        for _ in range(i % 3):
            w[rng.integers(0, 60)] ^= 1
        words.append(w if i % 4 else rng.integers(0, 2, 60))
    msg, ok = rs.decode(np.stack(words))
    for w, m, o in zip(words, msg, ok):
        d = rs_decode(code, w)
        assert d.ok == o
        if o:
            assert (d.message_bits == m).all()


def test_reference_encode_is_systematic_and_decodes():
    rs = reference.RS(4, 15, 12)
    bits = np.random.default_rng(1).integers(0, 2, 48)
    cw = rs.encode(bits)
    assert (cw[:48] == bits).all()
    bad = cw.copy()
    bad[7] ^= 1
    msg, ok = rs.decode(np.stack([cw, bad]))
    assert ok.all() and (msg == bits[None]).all()


def test_the_embedded_key_decodes_through_the_reference():
    from bench import workload

    code = reference.RS(4, 15, 12)
    key = np.random.default_rng(2).integers(0, 2, 48)
    p = workload.make_params(jax.random.key(1), channels=8, depth=2,
                             n_bits=60, tile=64, head_scale=0.1)
    pool = np.asarray(workload.make_pool(
        jax.random.key(2), p["corr"], jnp.asarray(code.encode(key)), n=4,
        size=288, crop=256, tile=64, embed_rms=0.06))
    keys = reference.image_keys(5, np.arange(4), np.zeros(4, int))
    lg = np.asarray(reference.tile_logits(p, pool, keys, resize=288,
                                          crop=256, tile=64))
    msg, ok = code.decode((lg > 0).astype(np.int32))
    assert ok.all() and (msg == key[None]).all()
