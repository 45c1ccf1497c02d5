"""Trace reduction on a small trace recorded on one TPU v5e.

``data/tiny.xplane.pb.gz``: a traced window of a tiny packed-cache LM (4
decode ticks, 12 prefill chunks) and a tiny spiking ViT (2 one-shot
prefills) served through the engine with the fused kernels, with the
harness's ``bench/window`` span; the HLO-proto plane was dropped, source
paths in operation metadata made relative, and the file compressed.
"""
import gzip
import importlib.util

import pytest

from bench import trace_reduce as tr

from conftest import DATA, ROOT


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / "tiny.xplane.pb.gz").read_bytes()))
    return tr.reduce_file(path)


def reader_module(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_window_is_the_bench_span_and_busy_fits_in_it(summary):
    assert summary.window_s == pytest.approx(1.461314144)
    assert 0 < summary.busy_s < summary.window_s
    assert summary.busy_s == pytest.approx(0.001850726)


def test_busy_and_idle_gaps_tile_the_window(summary):
    idle = sum(length for _, length in summary.idle_gaps()) / 1e9
    assert idle + summary.busy_s == pytest.approx(summary.window_s, rel=1e-9)


def test_program_runs_are_labelled_by_the_span_that_launched_them(summary):
    assert len(summary.module_runs("repro/decode_dispatch")) == 4
    assert len(summary.module_runs("repro/prefill_chunk_dispatch")) == 12
    assert len(summary.module_runs("repro/prefill_dispatch")) == 2


def test_kernels_are_told_apart_by_their_query_operand(summary):
    ssa = [e for e in summary.ops if tr.op_base(e) == "ssa_attention"]
    kinds = sorted(tr.call_operand_dtypes(e)[3] for e in ssa)
    assert kinds.count("u32") == 80 and kinds.count("f32") == 16
    packed = reader_module("kernel.ssa_packed_roofline")._kernel
    assert summary.op_seconds(packed) == pytest.approx(0.001036798)
    # the ViT's dense calls (float query spikes) are not the packed kernel's
    dense = sum(e.dur for e in ssa if tr.call_operand_dtypes(e)[3] == "f32") / 1e9
    assert summary.op_seconds(packed) + dense == pytest.approx(
        summary.op_seconds(lambda e: tr.op_base(e) == "ssa_attention"))
    assert dense == pytest.approx(1.4009e-05)


def test_breakdown_names_operations_and_host_spans(summary):
    b = summary.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "ssa_attention"
    assert not any(name == "while" for name, _ in b["device_ops"])
    labels = {name for name, _ in b["idle_gaps"]}
    assert {"bench/step", "repro/decode_dispatch"} <= labels


def test_idle_share_reader(summary):
    from types import SimpleNamespace

    read = reader_module("device.idle_share.lm").read
    share = read(SimpleNamespace(trace=summary))
    assert share == pytest.approx(100 * (1 - 0.001850726 / 1.461314144))


def test_op_names_and_operands_parse():
    ev = tr.Ev("%ssa_attention.39 = f32[256,128,128]{2,1,0:T(8,128)} custom-call("
               "u32[256,1]{1,0:T(8,128)S(1)} %a, s32[256,128,1]{2,1,0} %b, "
               "s32[256,1,1024]{2,1,0} %c, u32[256,128,4]{2,1,0} %d, "
               "u32[256,1024,4]{2,1,0} %e, u32[256,1024,4]{2,1,0} %f), "
               "custom_call_target=\"tpu_custom_call\"", 0.0, 1.0)
    assert tr.op_base(ev) == "ssa_attention"
    assert tr.call_operand_dtypes(ev) == ["u32", "s32", "s32", "u32", "u32", "u32"]
    assert tr.op_base(tr.Ev("%copy-done.2 = u32[8] copy-done(...)", 0, 1)) == "copy-done"
