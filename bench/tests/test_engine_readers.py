"""The scheduler-layer readers: programs found by the names the engine
gives them (``jit_serve_<key>``), on synthetic traces and on a trace
recorded before the engine named its programs."""
import gzip
from types import SimpleNamespace

import pytest

from bench import harness
from bench import trace_reduce as tr

from conftest import DATA

MS = 1e6  # ns


def mod(name, start_ms, dur_ms):
    return tr.Ev(f"{name}(1234567)", start_ms * MS, dur_ms * MS)


def summary(modules, window_ms=3000.0):
    return tr.TraceSummary((0.0, window_ms * MS), [], modules, [], 0.0)


def chunk_events(*spans):
    from repro.obs import Tracer

    t = Tracer()
    t.emit("decode_tick", tick=0, active=1)
    for c0, c1 in spans:
        t.emit("prefill_chunk", tick=0, uid=7, c0=c0, c1=c1, bucket=16, width=4)
    return t.events()


DEC, CHUNK = "jit_serve_decode_seeded", "jit_serve_chunk"
# two decode ticks, an admission of three chunks, two more decode ticks;
# the eager argmax after each tick is no engine program
TICKS = [mod(DEC, 0, 600), mod("jit__argmax", 600.5, 0.1), mod(DEC, 606, 600),
         mod(CHUNK, 1210, 90), mod(CHUNK, 1305, 95), mod(CHUNK, 1402, 40),
         mod(DEC, 1450, 600), mod(DEC, 2058, 600)]


def test_decode_gap_skips_the_gaps_an_admission_falls_in():
    read = harness.load_reader("engine.decode_gap_ms")
    # 606 - 600 and 2058 - 2050; the gaps around the chunks are left out
    assert read(SimpleNamespace(trace=summary(TICKS))) == pytest.approx(7.0)


def test_decode_gap_skips_a_draft_program_between_ticks():
    read = harness.load_reader("engine.decode_gap_ms")
    draft = [mod(DEC, 0, 600), mod("jit_serve_draft_decode_seeded", 601, 2),
             mod(DEC, 606, 600), mod(DEC, 1210, 600)]
    assert read(SimpleNamespace(trace=summary(draft))) == pytest.approx(4.0)


def test_prefill_share_is_chunk_device_time_over_the_window():
    read = harness.load_reader("engine.prefill_share")
    assert read(SimpleNamespace(trace=summary(TICKS))) == pytest.approx(
        100.0 * (90 + 95 + 40) / 3000.0)


def test_prefill_token_time_is_over_unpadded_prompt_tokens():
    read = harness.load_reader("step.prefill_token_ms")
    # chunks of 16, 16 and a final 5 tokens (padded to a bucket of 8)
    run = SimpleNamespace(trace=summary(TICKS),
                          events=chunk_events((0, 16), (16, 32), (32, 37)))
    assert read(run) == pytest.approx((90 + 95 + 40) / 37.0)
    run.events = chunk_events()
    assert read(run) is None


@pytest.mark.parametrize("name", ["engine.decode_gap_ms", "engine.prefill_share",
                                  "step.prefill_token_ms"])
def test_readers_find_nothing_without_named_programs(name, tmp_path):
    read = harness.load_reader(name)
    anonymous = [mod("jit__lambda", 0, 600), mod("jit__lambda", 606, 600),
                 mod("jit__lambda", 1210, 90)]
    events = chunk_events((0, 16))
    assert read(SimpleNamespace(trace=summary(anonymous), events=events)) is None
    assert read(SimpleNamespace(trace=None, events=events)) is None
    # a trace recorded on the chip before the engine named its programs
    path = tmp_path / "tiny.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / "tiny.xplane.pb.gz").read_bytes()))
    old = tr.reduce_file(path)
    assert read(SimpleNamespace(trace=old, events=events)) is None
