"""The benchmark's arithmetic: FLOP and byte counts against hand counts at
the two configurations' shapes, the peak table, and the metric
arithmetic on hand-made request records."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import stats, work  # noqa: E402
from chipbench.lookup import Lookup  # noqa: E402
from chipbench.window import Rec  # noqa: E402

FAM = Lookup().family("qwen2")


def cfg(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


# hand counts: q, k, v, o projections d*dh*(2h + 2kvh), SwiGLU 3*d*f
@pytest.mark.parametrize("name,per_layer,head", [
    ("qwen2-0.5b", 896 * 64 * (28 + 4) + 3 * 896 * 4864, 896 * 151936),
    ("qwen2-vl-7b-14l", 3584 * 128 * (56 + 8) + 3 * 3584 * 18944,
     3584 * 152064),
    ("qwen2-vl-7b", 3584 * 128 * (56 + 8) + 3 * 3584 * 18944,
     3584 * 152064)])
def test_layer_params_and_decode_flops(name, per_layer, head):
    c = cfg(name)
    assert FAM.layer_matmul_params(c) == per_layer
    n = c["num_hidden_layers"]
    h, dh = c["num_attention_heads"], 128 if "vl" in name else 64
    want = 2 * (n * per_layer + head) + 4 * n * h * dh * 1000
    assert work.decode_flops(FAM, c, [1000]) == want
    assert work.decode_flops(FAM, c, [1000, 1000]) == 2 * want


def test_published_sizes():
    assert FAM.layer_matmul_params(cfg("qwen2-0.5b")) == 14_909_440
    # 233.05M in a qwen2-vl-7b layer (its biases and norms aside)
    assert FAM.layer_matmul_params(cfg("qwen2-vl-7b")) == 233_046_016
    # K and V of a token: 24 layers x 2 KV heads x 64; 28 x 4 x 128
    assert FAM.kv_bytes_per_token(cfg("qwen2-0.5b")) == 12_288
    assert FAM.kv_bytes_per_token(cfg("qwen2-vl-7b")) == 4 * 14_336


def test_prefill_flops():
    c = cfg("qwen2-0.5b")
    n, p = 100, 500
    ctx = p * n + n * (n + 1) / 2
    want = (2 * 24 * 14_909_440 * n + 4 * 24 * 14 * 64 * ctx
            + 2 * 896 * 151936)
    assert work.prefill_flops(FAM, c, n, p) == pytest.approx(want)


def test_paged_decode_work():
    c = cfg("qwen2-0.5b")
    flops, byts = work.paged_decode_work(FAM, c, np.array([100, 300]))
    # per layer: K and V of 400 tokens, 2 heads of 64, bf16; q and out of
    # 14 heads for 2 rows; the new token's K and V for 2 rows
    kv = 2 * 400 * 2 * 64 * 2
    qo = 2 * 2 * 14 * 64 * 2 + 2 * 2 * 2 * 64 * 2
    assert byts == 24 * (kv + qo)
    assert flops == 24 * 4 * 14 * 64 * (400 + 2)


def test_flash_prefill_work():
    c = cfg("qwen2-vl-7b-14l")
    flops, byts = work.flash_prefill_work(c, 512)
    assert flops == 14 * 4 * 28 * 128 * 512 * 513 / 2
    assert byts == 14 * (2 * 28 + 2 * 4) * 512 * 128 * 2


def test_roofline_and_peaks():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_s"] == 819e9
    assert work.roofline_seconds(197e12, 1.0, pk) == (1.0, "compute")
    assert work.roofline_seconds(1.0, 819e9, pk) == (1.0, "memory")
    with pytest.raises(KeyError):
        work.peaks("TPU v9000")


def test_percentile_tpot_rate():
    assert stats.percentile([], 50) is None
    assert stats.percentile([3, 1, 2], 50) == 2
    assert stats.percentile(range(101), 95) == 95
    assert stats.percentile([0, 10], 95) == pytest.approx(9.5)
    assert np.isclose(stats.percentile(list(range(1, 21)), 95),
                      np.percentile(np.arange(1, 21), 95))
    assert stats.tpot(1.0, 2.0, 11) == pytest.approx(0.1)
    assert stats.tpot(1.0, 1.0, 1) is None
    assert stats.rate(500, 10) == 50


class _Drv:
    def __init__(self, recs, measured, t_end, stopped):
        self.recs, self.measured = recs, measured
        self.t_end, self.stopped = t_end, stopped


class _Rec:
    pass


def hand_run():
    """Three requests due at 0, 1 and 2 s of a 10 s window starting at
    100 s; the third never got a token before the run stopped at 115.  A
    lead-in request due at 95 s delivered one of its tokens inside the
    window; it is not measured."""
    recs = {
        0: Rec(0, due=100.0, prompt_len=64, budget=5, measured=True,
               admitted=100.01, first=100.05, last=100.45, tokens=5,
               tokens_in_window=5, done=True),
        1: Rec(1, due=101.0, prompt_len=64, budget=3, measured=True,
               admitted=101.2, first=101.3, last=101.5, tokens=3,
               tokens_in_window=3, done=True),
        2: Rec(2, due=102.0, prompt_len=64, budget=9, measured=True),
        3: Rec(3, due=95.0, prompt_len=64, budget=6, measured=False,
               admitted=95.1, first=95.2, last=100.5, tokens=6,
               tokens_in_window=1, done=True),
    }
    r = _Rec()
    r.recs, r.seconds, r.setup_s = recs, 10.0, 42.0
    r.drv = _Drv(recs, [0, 1, 2], t_end=110.0, stopped=115.0)
    r.sched = {"prompt_tokens": 400, "prefilled_tokens": 100}
    r.lowered_in_window = 0
    r.trace = r.tracer = None
    return r


@pytest.fixture(scope="module")
def lk():
    return Lookup()


def test_end_to_end_readers_on_hand_records(lk):
    r = hand_run()
    # TTFT 0.05, 0.3 and (unfinished) 13 s
    assert lk.reader("ttft_p50_ms")(r) == pytest.approx(300.0)
    assert lk.reader("ttft_p95_ms")(r) == pytest.approx(
        stats.percentile([50.0, 300.0, 13000.0], 95))
    # TPOT 0.4/4 = 0.1 and 0.2/2 = 0.1
    assert lk.reader("tpot_p95_ms")(r) == pytest.approx(100.0)
    # every token delivered in the window, the lead-in's one included
    assert lk.reader("output_tokens_per_s")(r) == pytest.approx(0.9)
    assert lk.reader("setup_s")(r) == 42.0


def test_per_layer_host_readers_on_hand_records(lk):
    r = hand_run()
    assert lk.reader("queue_wait_p95_ms")(r) == pytest.approx(
        stats.percentile([10.0, 200.0, 13000.0], 95))
    assert lk.reader("prefix_hit_token_share")(r) == pytest.approx(75.0)
    assert lk.reader("compiles_in_window")(r) == 0
    for name in ("device_idle_share", "segment_gap_ms", "decode_step_ms",
                 "decode_step_mfu", "prefill_mfu", "prefill_ms_per_ktok",
                 "paged_decode_roofline", "flash_prefill_roofline",
                 "collective_exposed_share"):
        assert lk.reader(name)(r) is None      # nothing traced: no number


class _Tracer:
    def __init__(self, stalls):
        self._stalls = stalls

    def stalls(self):
        return self._stalls


def test_traced_queue_wait_leaves_out_the_profilers_stall(lk):
    """A request whose wait for admission overlaps the profiler's start
    or stop waits for the profiler: a traced run's queue wait leaves it
    out, and keeps every other request, due before or after."""
    r = hand_run()
    r.tracer = _Tracer([(101.1, 101.15), (120.0, 121.0)])
    assert lk.reader("queue_wait_p95_ms")(r) == pytest.approx(
        stats.percentile([10.0, 13000.0], 95))
    r.tracer = _Tracer([(100.005, 100.02), (101.1, 101.15)])
    assert lk.reader("queue_wait_p95_ms")(r) == pytest.approx(13000.0)
