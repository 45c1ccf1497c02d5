"""The correctness check at a size a CPU holds: the plain reference agrees
with the program, its control (one precision lower) fails the limit, and a
run whose timed path is broken comes out not correct.

Each test drives a whole run through ``harness.run_cell`` with the look for
a chip skipped, on tiny configurations of the served families.
"""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness

from conftest import load

PEAKS = harness.load_peaks("TPU v5 lite")   # arithmetic only; CPU reads no device


def spec(config, mix, limits):
    return harness.Spec("tiny", {"chips": 1}, load(config), load(mix),
                        load(limits), [], [])


def run(config, mix, *, others=(), hook=None, seed=2**31 + 5,
        limits="tiny-limits.json"):
    return harness.run_cell(spec(config, mix, limits), seed=seed, seconds=2.0,
                            trace=False,
                            t_start=time.perf_counter(), devices=None,
                            others=others, system_hook=hook, peaks=PEAKS)


def test_reference_reproduces_the_float32_program():
    """Served in float32, every served token is the reference's first choice."""
    r = run("tiny-lm-f32.json", "tiny-closed.json")
    assert r["correct"]
    assert r["checks"]["widest_logit_gap"]["value"] == 0.0
    assert r["checks"]["checked_tokens"]["value"] >= 20


def test_control_fails_where_the_program_passes():
    """A float32 configuration: the program reads 0, the bfloat16 control
    (one precision lower) fails the limit.  (At this size a bfloat16
    program and its int8 control read alike, so the bfloat16 cell's
    control readings come from the chip at the cell's own size.)"""
    r = run("tiny-lm-f32.json", "tiny-open.json", others=("bf16",),
            limits="tiny-limits-f32.json")
    control = r["judged"]["bf16"]
    assert r["correct"]
    assert r["checks"]["widest_logit_gap"]["value"] == 0.0
    assert control["correct"] is False
    assert (control["checks"]["widest_logit_gap"]["value"]
            > control["checks"]["widest_logit_gap"]["limit"])


def _token_altered(system):
    """Every sampled token is the greedy choice plus one."""
    make = system.engine

    def engine(tracer=None):
        eng = make(tracer)
        eng.sampler = lambda key, logits: (
            (jnp.argmax(logits, axis=-1) + 1) % logits.shape[-1]).astype(jnp.int32)
        return eng

    system.engine = engine


def _state_unchanged(system):
    """The decode step (and so every prefill chunk) returns its cache as
    it came in."""
    model = system.model
    step = model.decode_step

    def decode_step(params, batch, cache, cache_index, rng=None, seeds=None,
                    logits_at=None):
        logits, _ = step(params, batch, cache, cache_index, rng=rng, seeds=seeds,
                         logits_at=logits_at)
        return logits, cache

    model.decode_step = decode_step


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged])
def test_a_broken_timed_path_is_not_correct(fault):
    r = run("tiny-lm.json", "tiny-closed.json", hook=fault)
    assert not r["correct"]
    assert (r["checks"]["widest_logit_gap"]["value"]
            > r["checks"]["widest_logit_gap"]["limit"])


def test_unanswered_requests_are_not_correct(monkeypatch):
    """An open-loop answer that never comes fails the run."""
    monkeypatch.setattr(harness, "DRAIN_LIMIT_S", 0.0)

    def stall(system):
        make = system.engine

        def engine(tracer=None):
            eng = make(tracer)
            real = eng.step
            eng.step = lambda: (time.sleep(0.05), real())[1]
            return eng

        system.engine = engine

    r = run("tiny-lm.json", "tiny-open.json", hook=stall)
    assert r["failed"] > 0
    assert not r["correct"]
    jax.clear_caches()


def test_cached_spikes_match_the_reference_and_the_control_does_not():
    """The packed K/V planes the engine caches for in-flight requests: the
    first layer's agree bit for bit with the reference's, and the int8
    control (one precision below bfloat16) differs many times more than
    the program in the layers compared."""
    r = run("tiny-lm.json", "tiny-closed.json", others=("int8", "int8_head", "int8_deep"))
    prog = r["judged"]["program"]["readings"]["kv_mismatch_by_layer"]
    ctrl = r["judged"]["int8"]["readings"]["kv_mismatch_by_layer"]
    assert r["correct"]
    assert r["checks"]["kv_checked_requests"]["value"] == 2
    assert prog[0] == 0.0
    assert max(ctrl) > 5 * max(prog)
    assert r["checks"]["kv_spike_mismatch"]["value"] == max(prog)
    # the control, judged in the program's place, is not correct
    assert r["judged"]["int8"]["correct"] is False
    # int8 in the head alone leaves every layer's spikes as the reference's
    assert r["judged"]["int8_head"]["readings"]["kv_mismatch_by_layer"] == [0.0, 0.0]
    assert set(r["judged"]["int8_deep"]["checks"]) == set(r["checks"])
