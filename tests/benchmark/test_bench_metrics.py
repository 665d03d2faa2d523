"""Metric arithmetic on synthetic records, the FLOP and byte counts from
shapes, and the trace reduction on synthetic events."""

from __future__ import annotations

import importlib
import json
import types
from pathlib import Path

import numpy as np
import pytest

from benchmarks.serving import bench as B
from benchmarks.serving import flops, profile_reduce
from benchmarks.serving.summary import percentile

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ROOT / "benchmarks" / "serving" / "configs"


def metric(name):
    return importlib.import_module(f"benchmarks.serving.metrics.{name}").read


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["config"]


def view(records, waves=(), **kw):
    v = dict(records=records, waves=list(waves), lateness_s=[], seconds=10.0,
             window_end_s=max(r["end_s"] for r in records), compiles=0,
             buckets=[], setup_s=3.5, trace=None, peaks=None, slots=4,
             family="dense", model=model("granite-3-2b"))
    v.update(kw)
    return types.SimpleNamespace(**v)


def rec(due, start, end, gen=10, prompt=128):
    return {"rid": 0, "due_s": due, "start_s": start, "end_s": end,
            "gen_len": gen, "prompt_len": prompt, "tokens": None}


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 101])
def test_percentile_matches_numpy_linear(q, n):
    x = np.random.default_rng(n).exponential(size=n)
    assert percentile(x, q) == pytest.approx(float(np.percentile(x, q)))


def test_latency_percentiles_cover_every_request():
    # 20 requests: latencies 1..20 s; the p90 is over all of them
    recs = [rec(0.0, 0.0, float(i)) for i in range(1, 21)]
    v = view(recs)
    assert metric("request_latency_p90_s")(v) == pytest.approx(18.1)
    assert metric("request_tail_p90_s")(v) == pytest.approx(18.1)
    assert metric("request_latency_p50_s")(v) == pytest.approx(10.5)


def test_rate_counts_all_tokens_over_window_and_drain():
    # requests due in a 10 s window; the last completes at 14 s (drain)
    recs = [rec(1.0, 1.0, 5.0, gen=100), rec(9.0, 9.5, 14.0, gen=180)]
    assert metric("output_tokens_per_s")(view(recs)) == pytest.approx(280 / 14)


def test_queue_wait_is_wave_start_minus_due():
    recs = [rec(0.0, 0.0, 3.0), rec(1.0, 3.0, 6.0), rec(2.0, 3.0, 6.0)]
    assert metric("queue_wait_p90_s")(view(recs)) == pytest.approx(
        float(np.percentile([0.0, 2.0, 1.0], 90)))


def test_slot_occupancy_weights_waves_by_steps():
    waves = [{"decode_steps": 100, "slot_utilization": 0.5},
             {"decode_steps": 300, "slot_utilization": 1.0},
             {"decode_steps": 0, "slot_utilization": 0.0}]
    v = view([rec(0, 0, 1)], waves)
    assert metric("slot_occupancy")(v) == pytest.approx((50 + 300) / 400)
    assert metric("slot_occupancy")(view([rec(0, 0, 1)], [])) is None


def test_setup_and_compiles_pass_through():
    v = view([rec(0, 0, 1)], compiles=3)
    assert metric("setup_s")(v) == 3.5
    assert metric("compiles_in_window")(v) == 3


def test_trace_metrics_read_nothing_without_a_trace():
    v = view([rec(0, 0, 1)])
    for name in ("admit_ms", "decode_step_ms", "decode_step_roofline",
                 "device_idle_share", "mfu"):
        assert metric(name)(v) is None


def test_published_sizes():
    g, m = model("granite-3-2b"), model("mamba2-1.3b")
    assert flops.dense_weight_params(g) * 2 == pytest.approx(5.07e9, rel=2e-3)
    assert flops.ssm_weight_params(m) * 2 == pytest.approx(2.69e9, rel=1e-2)
    # KV cache: 40 layers x (K, V) x 8 heads x 64 x bf16 = 80 KB per token
    _, b1 = flops.dense_decode_step(g, 1, 1000)
    _, b2 = flops.dense_decode_step(g, 1, 1001)
    assert b2 - b1 == 40 * 2 * 8 * 64 * 2
    # per slot, read and written: 48 layers x (4096 x 128 float32 state,
    # 100.7 MB in all, and 3 rows of 4096 + 256 bf16 convolution buffer)
    _, s1 = flops.ssm_decode_step(m, 1, 128)
    _, s0 = flops.ssm_decode_step(m, 0, 128)
    assert (s1 - s0) / 2 == 48 * (4096 * 128 * 4 + 3 * 4352 * 2)


def test_decode_and_request_flops_agree():
    g = model("granite-3-2b")
    f, _ = flops.dense_decode_step(g, 4, 300)
    assert f == pytest.approx(4 * flops.dense_token_flops(g, 300))
    # a request: prefill of its prompt, then gen-1 tokens at growing context
    one = flops.request_flops("dense", g, 100, 3)
    assert one == pytest.approx(flops.dense_prefill_flops(g, 100)
                                + flops.dense_token_flops(g, 101)
                                + flops.dense_token_flops(g, 102))
    # 2 operations per weight per token dominate
    assert flops.dense_token_flops(g, 1) == pytest.approx(
        2 * flops.dense_weight_params(g), rel=0.01)


def test_roofline_and_mfu_stay_under_peak():
    g = model("granite-3-2b")
    peaks = flops.peaks("TPU v5 lite")
    # a decode call that took exactly its HBM-bound least time reads 100 %
    _, by = flops.dense_decode_step(g, 4, 512)
    secs = by / peaks["hbm_bytes_per_s"]
    trace = types.SimpleNamespace(program=lambda name: (3, 3 * secs),
                                  busy_s=1.0, window_s=4.0)
    v = view([rec(0, 0, 100.0, gen=10)], trace=trace, peaks=peaks,
             buckets=[512, 512, 512])
    assert metric("decode_step_roofline")(v) == pytest.approx(100.0)
    assert 0 < metric("mfu")(v) < 1
    assert metric("device_idle_share")(v) == pytest.approx(0.75)


def test_unknown_chip_has_no_peaks():
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_reduction_busy_union_gaps_and_programs():
    ms = 1_000_000
    host = [("window", 0, 100 * ms), ("SlotScheduler.run", 0, 60 * ms),
            ("wait_for_arrival", 60 * ms, 90 * ms),
            ("SlotScheduler.run", 90 * ms, 100 * ms)]
    dev = {"ops": [("fusion.1", 5 * ms, 20 * ms), ("fusion.2", 10 * ms, 30 * ms),
                   ("fusion.1", 40 * ms, 50 * ms), ("copy", 95 * ms, 120 * ms)],
           "modules": [("jit_step(7)", 5 * ms, 30 * ms),
                       ("jit_step(9)", 40 * ms, 50 * ms),
                       ("jit__admit(3)", 95 * ms, 120 * ms)]}
    r = profile_reduce.reduce_events(host, [dev])
    assert r.window_s == pytest.approx(0.1)
    # busy: [5, 30) + [40, 50) + [95, 100) = 40 ms
    assert r.busy_s == pytest.approx(0.040)
    assert r.program("jit_step") == (2, pytest.approx(0.035))
    assert r.program("jit__admit") == (1, pytest.approx(0.025))
    assert r.program("nothing") == (0, 0.0)
    # idle: [0,5) run, [30,40) run, [50,95) midpoint 72.5 -> waiting
    assert r.gaps[0] == ("wait_for_arrival", pytest.approx(0.045))
    assert sorted(g[1] for g in r.gaps) == pytest.approx([0.005, 0.010, 0.045])
    b = r.breakdown()
    assert b["device_ops"][0] == ["jit_step/fusion.1", pytest.approx(0.025)]
    assert ["jit__admit/copy", pytest.approx(0.025)] in b["device_ops"]
    assert len(b["idle_gaps"]) == 3


def test_reduction_averages_busy_over_chips():
    host = [("window", 0, 100)]
    a = {"ops": [("x", 0, 100)], "modules": []}
    b = {"ops": [("x", 0, 50)], "modules": []}
    assert profile_reduce.reduce_events(host, [a, b]).busy_s == \
        pytest.approx(75e-9)
    with pytest.raises(ValueError):
        profile_reduce.reduce_events([], [a])


@pytest.mark.parametrize("gen,slots,want", [([5], 4, 5), ([3, 3, 3, 3], 2, 6),
                                            ([1, 9, 2], 8, 10)])
def test_wave_steps_bound(gen, slots, want):
    # ceil(sum(g - 1) / slots) + max(g - 1)
    assert B.wave_steps_bound(gen, slots) == want


def test_concat_groups_cover_every_concatenation():
    import jax
    import jax.numpy as jnp
    ops = {w: jnp.zeros((3, w), jnp.int32) for w in range(1, 41)}
    for group in B.concat_groups(40):
        jax.lax.concatenate([ops[w] for w in group], 1)
    col = jnp.ones((3, 1), jnp.int32)
    B.CompileCounter.install()
    c0 = B.CompileCounter.count
    for n in range(2, 41):
        jnp.concatenate([col] * n, axis=1)
    assert B.CompileCounter.count == c0


def test_vocab_rows_pad_as_the_published_model():
    from benchmarks.serving.reference.common import vocab_rows
    assert vocab_rows(model("mamba2-1.3b")) == 50288      # 50277 up to 16s
    assert vocab_rows(model("granite-3-2b")) == 49155     # no padding
    assert B.program_config(B.load_json(
        ROOT / "benchmarks/serving/configs/mamba2-1.3b.json")).vocab == 50288
