"""The expert-LM family: its counts at the published sizes, its readers on
synthetic traces, and a whole run on a tiny float32 configuration, where
the served tokens and the cached spikes of four pattern slots match the
plain reference."""
import json
import time
from types import SimpleNamespace

import pytest

from bench import harness
from bench import trace_reduce as tr
from bench.families import moe_lm

from conftest import ROOT, load

PEAKS = harness.load_peaks("TPU v5 lite")
MELLUM = json.loads(
    (ROOT / "bench" / "configs" / "mellum2-12b-a2.5b-ssa-packed-8l.json").read_text())
MS = 1e6  # ns


def test_active_weights_at_published_sizes():
    attention = 2304 * (32 + 2 * 4) * 128 + 32 * 128 * 2304
    assert attention == 21_233_664
    router, experts = 2304 * 64, 8 * 3 * 2304 * 896
    assert moe_lm.matmul_params(MELLUM) == 8 * (attention + router + experts)
    assert moe_lm.head_params(MELLUM) == 2304 * 98304


def test_window_aware_visible_keys():
    per_key = 32 * 4 * 4 * 128
    # layers 0-2 and 4-6 are window layers, 3 and 7 full
    assert moe_lm.ssa_ops(MELLUM, 2000, 2001) == per_key * (2 * 2001 + 6 * 1024)
    assert moe_lm.ssa_ops(MELLUM, 500, 501) == per_key * 8 * 501
    assert moe_lm.ssa_ops(MELLUM, 10, 3000) == per_key * 8 * 11
    ops = moe_lm.token_ops(MELLUM, 2000, 2001, logits=True)
    assert ops == (2 * moe_lm.matmul_params(MELLUM) + moe_lm.ssa_ops(MELLUM, 2000, 2001)
                   + 2 * 2304 * 98304)


def test_expert_call_counts_read_experts_not_all():
    ops, nbytes = moe_lm.expert_call(MELLUM, pairs=64, experts=40)
    assert ops == 6 * 2304 * 896 * 64
    assert nbytes == 2 * (3 * 2304 * 896 * 40 + 64 * (3 * 2304 + 3 * 896))


def _gmm(start_ms, dur_ms):
    return tr.Ev("%moe_gmm.3 = bf16[128,896]{1,0} custom-call(%x)", start_ms * MS, dur_ms * MS)


def _events(*stats):
    from repro.obs import Tracer

    t = Tracer()
    for kind, pairs, experts in stats:
        e = t.emit(kind, tick=0)
        e.data.update(moe_pairs=pairs, moe_experts=experts)
    t.emit("decode_tick", tick=1)       # a tick whose stats were never fetched
    return t.events()


def _runs(*kinds):
    """Program runs of ``kinds`` ("decode" / "chunk"), 10 ms apart, each
    launched from its engine span."""
    name = {"decode": ("jit_serve_decode_seeded", "repro/decode_dispatch"),
            "chunk": ("jit_serve_chunk", "repro/prefill_chunk_dispatch")}
    mods, host, enq = [], [], {}
    for i, kind in enumerate(kinds):
        prog, span = name[kind]
        mods.append(tr.Ev(f"{prog}({i})", i * 10 * MS, 5 * MS, {"run_id": i}))
        host.append(tr.Ev(span, i * 10 * MS - 1, 1, {}))
        enq[i] = i * 10 * MS - 0.5
    return mods, host, enq


def test_roofline_reads_expert_bytes_from_the_counter():
    read = harness.load_reader("kernel.moe_experts_roofline")
    events = _events(("decode_tick", [64, 64], [40, 30]),
                     ("prefill_chunk", [128, 128], [55, 56]))
    # four expert layers reading 30-56 experts each: ~2.7 ms at 819 GB/s
    ops = [_gmm(0, 2.0), _gmm(10, 2.0), tr.Ev("%fusion.1 = f32[]", 0, 5 * MS)]
    mods, host, enq = _runs("decode", "chunk")
    trace = tr.TraceSummary((0.0, 20 * MS), ops, mods, host, 0.0, enq)
    run = SimpleNamespace(trace=trace, events=events, config=MELLUM, peaks=PEAKS)
    bw = PEAKS["hbm_bytes_per_s"]
    want = sum(moe_lm.expert_call(MELLUM, p, e)[1] / bw
               for p, e in ((64, 40), (64, 30), (128, 55), (128, 56)))
    assert read(run) == pytest.approx(100.0 * want / 4e-3)
    assert 60.0 < read(run) < 75.0
    assert read(SimpleNamespace(trace=trace, events=_events(), config=MELLUM,
                                peaks=PEAKS)) is None
    assert read(SimpleNamespace(trace=None, events=events)) is None


def test_roofline_counts_only_the_runs_the_trace_holds():
    """Three decode ticks dispatched, one in the device trace: the counted
    bytes are one tick's, not three, so the share stays a share."""
    read = harness.load_reader("kernel.moe_experts_roofline")
    events = _events(*[("decode_tick", [64, 64], [40, 30])] * 3)
    mods, host, enq = _runs("decode")
    trace = tr.TraceSummary((0.0, 10 * MS), [_gmm(0, 2.0)], mods, host, 0.0, enq)
    run = SimpleNamespace(trace=trace, events=events, config=MELLUM, peaks=PEAKS)
    bw = PEAKS["hbm_bytes_per_s"]
    want = sum(moe_lm.expert_call(MELLUM, 64, e)[1] / bw for e in (40, 30))
    assert read(run) == pytest.approx(100.0 * want / 2e-3)
    assert read(run) < 100.0


def test_decode_expert_time_is_per_decode_run():
    read = harness.load_reader("moe.decode_expert_ms")
    dec = [tr.Ev("jit_serve_decode_seeded(1)", 0, 30 * MS, {"run_id": 1}),
           tr.Ev("jit_serve_chunk(2)", 40 * MS, 15 * MS, {"run_id": 2}),
           tr.Ev("jit_serve_decode_seeded(1)", 60 * MS, 30 * MS, {"run_id": 3})]
    ops = [_gmm(1, 2.0), _gmm(5, 3.0), _gmm(41, 4.0), _gmm(61, 2.5)]
    host = [tr.Ev("repro/decode_dispatch", 0, 1, {}), tr.Ev("repro/prefill_chunk_dispatch", 39 * MS, 1, {}),
            tr.Ev("repro/decode_dispatch", 59 * MS, 1, {})]
    trace = tr.TraceSummary((0.0, 100 * MS), ops, dec, host, 0.0,
                            {1: 0.5, 2: 39 * MS + 0.5, 3: 59 * MS + 0.5})
    assert read(SimpleNamespace(trace=trace)) == pytest.approx((2.0 + 3.0 + 2.5) / 2)
    assert read(SimpleNamespace(trace=tr.TraceSummary((0.0, 1.0), [], dec, host, 0.0))) is None


def test_kv_snapshot_reads_pattern_slots_in_layer_order():
    """Layer ``l`` is step ``l // 4`` of pattern slot ``l % 4``, and a window
    ring's rolled rows come out by position: window 4 on a 6-row ring of
    2-row pages, 9 tokens written, so positions 5-8 sit in ring rows 5, 0,
    1, 2."""
    import numpy as np

    steps, pages, page, t, kv, words = 2, 4, 2, 1, 1, 1
    ring_pos = np.full((pages, page), -1, np.int32)
    for p in range(3, 9):                    # the ring holds positions 3-8
        row = p % 6
        ring_pos[row // page, row % page] = p
    pools = []
    for slot in range(4):
        shape = (steps, pages, page, t, kv, words)
        ks = np.zeros(shape, np.uint32)
        for step in range(steps):
            # word = 100 * layer + position
            ks[step] = (100 * (4 * step + slot) + ring_pos)[..., None, None, None]
        pools.append({"pos": np.broadcast_to(ring_pos, (steps, pages, page)),
                      "ks": ks, "vs": ks + 1})
    req = SimpleNamespace(out_tokens=[7, 8, 9])
    rec = SimpleNamespace(req=req, planned=SimpleNamespace(prompt=np.arange(7, dtype=np.int32)))
    engine = SimpleNamespace(
        active={0: req}, cache=pools, slot_pos=np.array([9]),
        tables=SimpleNamespace(pages=[[0, 1, 2, 3]]),
        model=SimpleNamespace(cfg=SimpleNamespace(attention=SimpleNamespace(sliding_window=4))))
    [(got_rec, tokens, planes)] = moe_lm.kv_snapshot(engine, [rec], layers=8)
    assert got_rec is rec
    assert tokens.tolist() == [0, 1, 2, 3, 4, 5, 6, 7, 8]
    want = np.array([[100 * layer + p for p in range(5, 9)] for layer in range(8)])
    assert planes["ks"].shape == (8, 4, t, kv, words)
    assert (planes["ks"][..., 0, 0, 0] == want).all()
    assert (planes["vs"][..., 0, 0, 0] == want + 1).all()


def test_tiny_expert_lm_matches_the_reference():
    """Float32, window 16 on a 32-row ring, prompts of 20-80 tokens: every
    served token is the reference's first choice, and the cached spikes of
    layers 0-3 (three window slots and the full one, read by position out
    of their own pools) equal the reference's bit for bit."""
    spec = harness.Spec("tiny", {"chips": 1}, load("tiny-moe-f32.json"),
                        load("tiny-closed.json"), load("tiny-moe-limits-f32.json"), [], [])
    r = harness.run_cell(spec, seed=2**31 + 9, seconds=2.0, trace=False,
                         t_start=time.perf_counter(), devices=None, peaks=PEAKS)
    assert r["correct"], r["checks"]
    assert r["checks"]["widest_logit_gap"]["value"] == 0.0
    assert r["checks"]["kv_checked_requests"]["value"] == 2
    assert r["checks"]["kv_spike_mismatch"]["value"] == 0.0
