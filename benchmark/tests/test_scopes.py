"""``harness/scopes.py``: the arithmetic on lists written by hand, on a
piece of a chip trace recorded after the program got its names (PR 24)
and kept beside this file, and the files of the metrics that read it."""

import glob
import json
import os
import types

import pytest

from benchmark import manifest
from benchmark.harness import cell
from benchmark.harness import scopes as S
from benchmark.roofline import flash

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e-3
POOL = ((4, 8, 4097, 16, 128), (8, 4097, 16, 128))
STEP = "jit(dstpu_decode)/while/body/closed_call/"


def op(text, start, dur, path=""):
    return S.op_from_event(text, start * MS, dur * MS, path)


def hand_made():
    """10 ms on one chip: a 9 ms ``while`` (unscoped) that holds 2 ms of
    an expert product, 1 ms of a page write, 1.5 ms of a whole-pool copy
    the compiler put in (no path), 0.5 ms of a one-layer slice of the
    pool (leading 1), 1 ms of a Mosaic kernel under ``kv_attend`` and
    1 ms of an unscoped copy of a weight; then 0.9 ms idle and a last
    copy of 0.1 ms.  Two steps of the host: 0-6 ms and 6-10 ms."""
    ops = [
        op("%while.4 = (s32[], bf16[4,8,4097,16,128]{4,3,2,1,0}) while("
           "(s32[], bf16[4,8,4097,16,128]{4,3,2,1,0}) %tuple.1), "
           "condition=%c, body=%b", 0, 9),
        op("%fusion.189 = bf16[64,8,14336]{2,0,1} fusion(bf16[4,8,4096,"
           "14336]{3,2,1,0} %p.1, bf16[64,1,4096]{2,1,0} %p.2), kind=kOutput,"
           " calls=%fused_computation.9", 0.5, 2,
           STEP + "mlp/moe_ffn/vmap()/dot_general:"),
        op("%fusion.7 = bf16[8,4097,16,128]{3,2,1,0} fusion(bf16[8,4097,16,"
           "128]{3,2,1,0} %p.3), kind=kLoop, calls=%fused_computation.2",
           2.5, 1, STEP + "kv_write/dynamic_update_slice:"),
        op("%copy.122 = bf16[4,8,4097,16,128]{4,3,2,1,0} copy(bf16[4,8,4097,"
           "16,128]{4,3,2,1,0} %p.4)", 3.5, 1.5),
        op("%constant_dynamic-slice_fusion.13 = bf16[1,8,4097,16,128]"
           "{4,3,2,1,0} fusion(bf16[4,8,4097,16,128]{4,3,2,1,0} %p.5), "
           "kind=kLoop, calls=%fused_computation.3", 5, 0.5),
        op('%dstpu_paged_chunk_v2.13 = bf16[512,8,128]{2,1,0} custom-call('
           's32[64,64]{1,0} %t, s32[64]{0} %s, bf16[512,8,128]{2,1,0} %q, '
           'bf16[8,4097,16,128]{3,2,1,0} %k, bf16[8,4097,16,128]{3,2,1,0} '
           '%v), custom_call_target="tpu_custom_call", operand_layout_'
           'constraints={s32[64,64]{1,0}}', 6, 1,
           STEP + "kv_attend/dstpu_paged_chunk_v2/pallas_call:"),
        op("%copy.91 = bf16[1,4096,4096]{2,1,0} copy(bf16[1,4096,4096]"
           "{2,1,0} %p.6)", 7.5, 1),
        op("%copy.99 = s32[2]{0} copy(s32[2]{0} %p.7)", 9.9, 0.1),
    ]
    ms = lambda ev: [(n, s * MS, d * MS) for n, s, d in ev]
    span = lambda n, s, d, **kw: S.Span(n, s * MS, d * MS, kw)
    return S.Scoped(
        ops={0: ops},
        programs={0: ms([("jit_dstpu_prefill", 0, 2), ("jit_concatenate",
                                                      2.1, 0.01),
                         ("jit_dstpu_decode", 2.2, 6.8)])},
        spans=[span("bench/step", 0, 6), span("dstpu/serving_step", 0.1, 5.8),
               span("dstpu/serving_admit", 0.1, 2.0),
               span("dstpu/request_admitted", 1.0, 0.0, request_id="7"),
               span("dstpu/serving_dispatch", 2.1, 0.4),
               span("dstpu/serving_token_sync", 2.5, 3.3),
               span("dstpu/serving_append", 5.8, 0.1),
               span("dstpu/serving_tick", 5.9, 0.05),
               span("bench/step", 6, 4), span("dstpu/serving_step", 6, 3.9),
               span("dstpu/serving_token_sync", 6, 3.0),
               span("dstpu/serving_append", 9.0, 0.9)])


def test_the_innermost_known_word_of_a_path_wins():
    assert S.scope_of(STEP + "mlp/moe_ffn/vmap()/dot_general:") == \
        ("moe_ffn", False)
    assert S.scope_of("jit(dstpu_train_step)/transpose(jvp(loss))/while/"
                      "body/closed_call/attn_qkv/dot_general") == \
        ("attn_qkv", True)
    assert S.scope_of("jit(dstpu_train_step)/jvp(loss)/mul") == \
        ("loss", False)
    # a function's name that merely holds a word is not the word
    assert S.scope_of("jit(_sample_rows)/jit(flash_attention)/exp") == \
        (None, False)
    assert S.scope_of("") == (None, False)


def test_an_hlo_line_gives_results_operands_and_the_kernels_name():
    k = hand_made().ops[0][5]
    assert k.kernel == "dstpu_paged_chunk_v2"
    assert k.name == "dstpu_paged_chunk_v2.13:bf16[512,8,128]_pallas"
    assert k.results == (("bf16", (512, 8, 128)),)
    assert [d for _, d in k.operands] == [
        (64, 64), (64,), (512, 8, 128), (8, 4097, 16, 128),
        (8, 4097, 16, 128)]           # the layout constraints are not operands
    w = hand_made().ops[0][0]
    assert w.kernel is None and len(w.results) == 2 == len(w.operands)


def test_self_time_by_scope_and_pool_shaped_copies():
    rows = S.by_scope(hand_made(), POOL)
    assert rows["moe_ffn"]["self_s"] == pytest.approx(2 * MS)
    assert rows["kv_write"]["self_s"] == pytest.approx(1 * MS)
    # the whole pool and one layer of it (a leading 1 squeezed)
    assert rows[S.KV_COPY]["self_s"] == pytest.approx(2 * MS)
    assert rows[S.KV_COPY]["ops"] == 2
    assert rows["kv_attend"]["self_s"] == pytest.approx(1 * MS)
    # the while's own 2 ms (9 - 2 - 1 - 1.5 - 0.5 - 1 - 1) and the copies
    # of a weight and of two integers stay unscoped: reported, not hidden
    assert rows[S.UNSCOPED]["self_s"] == pytest.approx(3.1 * MS)
    assert [n for n, _ in S.unscoped_ops(hand_made(), POOL)] == [
        "while.4:s32[]", "copy.91:bf16[1,4096,4096]", "copy.99:s32[2]"]
    # without a pool to compare with, the copies are unscoped too
    assert S.by_scope(hand_made())[S.UNSCOPED]["self_s"] == \
        pytest.approx(5.1 * MS)


def test_shares_of_the_window_and_of_busy():
    sc = hand_made()
    assert S.window_of(sc) == pytest.approx((0.0, 10 * MS))
    share = S.share_of_window(sc, ["kv_write", S.KV_COPY], POOL)
    assert share == pytest.approx(3 / 10)
    assert S.share_of_busy(sc, [S.UNSCOPED], POOL) == \
        pytest.approx(3.1 / 9.1)
    assert S.program_share(sc, ["dstpu_prefill", "dstpu_chunk"]) == \
        pytest.approx(2 / 10)
    assert S.kernel_seconds(sc) == {
        "dstpu_paged_chunk_v2": {"calls": 1,
                                 "seconds": pytest.approx(1 * MS)}}
    assert S.named(sc) and S.programs_named(sc, "dstpu_")
    bare = S.Scoped({0: [op("%copy.1 = bf16[8]{0} copy(bf16[8]{0} %p)",
                            0, 1)]}, {0: [("jit_step", 0, 1 * MS)]}, [])
    assert not S.named(bare) and not S.programs_named(bare, "dstpu_")


def test_backward_and_collective_seconds():
    path = "jit(dstpu_train_step)/transpose(jvp(loss))/mlp/dot_general"
    sc = S.Scoped({0: [
        op("%all-gather.3 = bf16[16,8]{1,0} all-gather(bf16[4,8]{1,0} %p)",
           0, 2, path),
        op("%fusion.1 = bf16[16,8]{1,0} fusion(bf16[16,8]{1,0} %p), "
           "kind=kLoop", 2, 1, "jit(dstpu_train_step)/jvp(loss)/mlp/mul"),
        op("%all-reduce.9 = f32[]{:T(128)} all-reduce(f32[] %p)", 3, 0.9,
           "jit(dstpu_train_step)/grad_clip/reduce_sum"),
        op("%all-reduce-done.9 = f32[]{:T(128)} all-reduce-done(f32[] %q)",
           3.9, 0.1, "jit(dstpu_train_step)/grad_clip/reduce_sum")]},
        {0: [("jit_dstpu_train_step", 0, 4 * MS),
             ("jit_dstpu_train_step", 4 * MS, 4 * MS)]}, [])
    rows = S.by_scope(sc)
    assert rows["mlp"] == {"self_s": pytest.approx(3 * MS), "ops": 2,
                           "collective_s": pytest.approx(2 * MS),
                           "backward_s": pytest.approx(2 * MS)}
    assert rows["grad_clip"]["collective_s"] == pytest.approx(1 * MS)
    # the median over the program's runs: 2 in the first (a -done is the
    # second half of one already counted), 0 in the second
    assert S.collectives_in_a_run(sc, "dstpu_train_step") == 1.0
    assert S.collectives_in_a_run(sc, "dstpu_decode") is None


def test_children_phases_and_coverage_of_a_step():
    sc = hand_made()
    steps = S.children(sc)
    assert [len(kids) for _, kids in steps] == [5, 2]
    assert S.coverage(sc) == pytest.approx((5.8 + 3.9) / (5.8 + 3.9))
    assert S.step_phases(sc, ["dstpu/serving_admit",
                              "dstpu/serving_dispatch"]) == \
        pytest.approx([2.4 * MS, 0.0])
    assert S.step_phases(sc, ["dstpu/serving_append"],
                         beside=["dstpu/serving_tick"]) == \
        pytest.approx([0.15 * MS, 0.9 * MS])
    # the prefill program started before the first step's span did
    assert S.programs_per_step(sc) == pytest.approx(1.0)   # 2 and 0
    empty = S.Scoped({}, {}, [])
    assert S.step_phases(empty, ["dstpu/serving_append"]) == []
    assert S.coverage(empty) is None
    assert S.programs_per_step(empty) is None


def test_idle_time_goes_to_the_innermost_span_at_each_instant():
    # the chip idles 9-9.9 ms: inside bench/step, dstpu/serving_step and
    # dstpu/serving_append; the innermost (it started last) takes it
    assert dict(S.idle_by_span(hand_made())) == {
        "dstpu/serving_append": pytest.approx(0.9 * MS)}
    # a gap that straddles two phases is split between them, and what
    # no span covers is said to be outside: idle 3-6 and 10-12 ms
    busy = lambda s, d: op("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), "
                           "kind=kLoop", s, d)
    sc = S.Scoped({0: [busy(0, 3), busy(6, 4), busy(12, 1)]}, {}, [
        S.Span("bench/step", 0, 10 * MS),
        S.Span("dstpu/serving_step", 1 * MS, 8 * MS),
        S.Span("dstpu/serving_inputs", 2 * MS, 2 * MS),
        S.Span("dstpu/serving_dispatch", 4 * MS, 1.5 * MS)])
    assert S.innermost(sc.spans)[:3] == [
        (0, pytest.approx(1 * MS), "bench/step"),
        (pytest.approx(1 * MS), pytest.approx(2 * MS), "dstpu/serving_step"),
        (pytest.approx(2 * MS), pytest.approx(4 * MS),
         "dstpu/serving_inputs")]
    assert dict(S.idle_by_span(sc)) == {
        "dstpu/serving_inputs": pytest.approx(1 * MS),
        "dstpu/serving_dispatch": pytest.approx(1.5 * MS),
        "dstpu/serving_step": pytest.approx(0.5 * MS),
        "outside_the_benchmark_s_spans": pytest.approx(2 * MS)}


def test_the_flash_kernels_floor():
    assert flash.scores(256, 256, True) == 256 * 257 // 2
    assert flash.scores(128, 512, True) == 128 * (2 * 512 - 128 + 1) // 2
    assert flash.scores(128, 512, False) == 128 * 512
    q, kv = ("bf16", (32, 256, 128)), ("bf16", (8, 256, 128))
    assert flash.flops("dstpu_flash_fwd", q[1], kv[1]) == \
        2 * 2 * 32 * (256 * 257 // 2) * 128
    assert flash.flops("dstpu_flash_bwd_dkv", q[1], kv[1]) == \
        2 * flash.flops("dstpu_flash_fwd", q[1], kv[1])
    shapes = (q, kv, kv, q, ("f32", (32, 256, 1)))
    assert flash.bytes_moved(shapes) == 2 * (2 * 32 + 2 * 8) * 256 * 128 \
        + 4 * 32 * 256
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # this short call is bound by its bytes, a long one by its products
    assert flash.floor_seconds("dstpu_flash_fwd", shapes, peaks) == \
        pytest.approx(flash.bytes_moved(shapes) / 819e9)
    long = (("bf16", (16, 8192, 128)),) * 4
    assert flash.floor_seconds("dstpu_flash_fwd", long, peaks) == \
        pytest.approx(flash.flops("dstpu_flash_fwd", long[0][1],
                                  long[1][1]) / 197e12)


def test_through_the_profilers_format_with_metadata_stats():
    """``tf_op`` lives on the event's *metadata*, which ProfileData does
    not hand out: written as a text proto and read back, paths, kernel
    names and span stats survive."""
    text = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules"
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8,4097,16,128]{3,2,1,0} fusion(bf16[8]{0} %p), kind=kLoop"
    stats { metadata_id: 7 str_value: "jit(dstpu_decode)/while/body/kv_write/scatter:" } } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = bf16[4,8,4097,16,128]{4,3,2,1,0} copy(bf16[4,8,4097,16,128]{4,3,2,1,0} %q)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_dstpu_decode(123)" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3"
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 0
      stats { metadata_id: 1 str_value: "42" } }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 100 }
  }
  event_metadata { key: 1 value { id: 1 name: "dstpu/request_admitted" } }
  event_metadata { key: 2 value { id: 2 name: "dstpu/serving_step" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(f)" } }
  stat_metadata { key: 1 value { id: 1 name: "request_id" } }
}
"""
    sc = S.from_text_proto(text)
    assert [o.path for o in sc.ops[0]] == [
        "jit(dstpu_decode)/while/body/kv_write/scatter:", ""]
    assert sc.programs[0][0][0] == "jit_dstpu_decode"
    assert sorted(s.name for s in sc.spans) == [
        "dstpu/request_admitted", "dstpu/serving_step"]
    assert {s.name: s.stats for s in sc.spans}[
        "dstpu/request_admitted"] == {"request_id": "42"}
    rows = S.by_scope(sc, POOL)
    assert set(rows) == {"kv_write", S.KV_COPY}


RECORDED = os.path.join(DATA, "v5e_scoped.xplane.txt")


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded trace beside the tests")
def test_recorded_piece_of_a_chip_trace_with_names():
    with open(RECORDED) as f:
        sc = S.from_text_proto(f.read())
    with open(os.path.join(DATA, "v5e_scoped.expected.json")) as f:
        want = json.load(f)
    pool = tuple(tuple(p) for p in want["pool"])
    rows = S.by_scope(sc, pool)
    assert {k: pytest.approx(v) for k, v in want["self_s"].items()} == \
        {k: r["self_s"] for k, r in rows.items()}
    assert S.share_of_busy(sc, [S.UNSCOPED], pool) == \
        pytest.approx(want["unscoped_share_of_busy"])
    assert S.program_share(sc, ["dstpu_prefill", "dstpu_chunk"]) == \
        pytest.approx(want["prefill_share_of_window"])
    assert S.coverage(sc) == pytest.approx(want["children_cover_step"])
    assert S.coverage(sc) >= 0.95
    assert S.programs_per_step(sc) == want["programs_per_step"]
    assert [n for n, _ in S.idle_by_span(sc)][:2] == want["idle_top"]
    assert {k: v["calls"] for k, v in S.kernel_seconds(sc).items()} == \
        want["kernel_calls"]
    marks = {s.name: s.stats for s in sc.spans if "request_" in s.name}
    assert marks == want["request_marks"]


# ------------------------------------------------ (d) the metric files
NEW_READERS = {"scope_share_of_device", "program_share_of_device",
               "flash_roofline", "collectives_per_step",
               "unscoped_share_of_busy", "step_phase_ms_p50",
               "programs_per_step"}


def test_every_metric_file_names_a_reader_and_the_manifest_holds():
    files = sorted(glob.glob(os.path.join(manifest.HERE, "metrics",
                                          "*.json")))
    e2e = [n for n, m in manifest._load("metrics").items() if "bound" in m]
    new = 0
    for path in files:
        name = os.path.basename(path)[:-len(".json")]
        m = cell.metric(name)
        assert callable(cell.reader(m["reader"])), name
        assert m["moves"] in e2e if "bound" not in m else True, name
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock"), name
        new += m["reader"] in NEW_READERS
        if m["reader"] == "step_phase_ms_p50":
            assert all(s.startswith("dstpu/serving_") for s in
                       m["args"]["spans"] + m["args"].get("beside", []))
        if m["reader"] == "scope_share_of_device":
            assert set(m["args"]["scopes_counted"]) <= set(S.VOCABULARY)
    assert new == 22
    for note in ("note_idle_by_span", "note_scopes"):
        assert callable(cell.reader(note))
    assert manifest.main(["--check"]) == 0


def test_readers_read_nothing_from_a_program_without_names():
    """The parent of PR 24 has no span, scope or kernel name: every new
    reader returns None there and does not raise."""
    run = types.SimpleNamespace(traced=None, trace_dir="/nonexistent",
                                window={"kind": "serve"}, peaks=None)
    for name in sorted(NEW_READERS) + ["note_idle_by_span", "note_scopes"]:
        args = next((cell.metric(os.path.basename(p)[:-5]).get("args", {})
                     for p in glob.glob(os.path.join(
                         manifest.HERE, "metrics", "*.json"))
                     if cell.metric(os.path.basename(p)[:-5])["reader"]
                     == name), {})
        assert cell.reader(name)(run, **args) is None, name
    # a trace there is, but nothing in it carries the program's names
    bare = S.Scoped({0: [op("%copy.1 = bf16[4,8,4097,16,128]{4,3,2,1,0} "
                            "copy(bf16[4,8,4097,16,128]{4,3,2,1,0} %p)",
                            0, 1, "jit(chunk_fn)/while/body/copy")]},
                    {0: [("jit_chunk_fn", 0, 1 * MS)]},
                    [S.Span("bench/step", 0, 1 * MS)])
    assert S.step_phases(bare, ["dstpu/serving_append"]) == []
    assert S.programs_per_step(bare) is None
    assert S.collectives_in_a_run(bare, "dstpu_train_step") is None
    assert not S.named(bare)
