"""What the cases of ``test_aot_tpu_compile*.py`` share: the widths every
cell has, and the readers of a compiled program's optimized HLO.  The
described chip itself (the ``topo`` and ``chip`` fixtures) is
``conftest.py``'s.  Not a pytest file (never collected)."""

import math
import re

import jax

DH, PAGE, TABLE_TOKENS = 128, 16, 4096


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _flash_kernels(hlo):
    """The flash kernels' calls in the optimized HLO, one name a call
    (a gradient's program spells them ``jvp_dstpu_flash_fwd_.1`` and
    ``transpose_jvp_dstpu_flash_bwd__.1``)."""
    return sorted(re.findall(
        r"%\w*?(dstpu_flash_(?:fwd|bwd_dq|bwd_dkv|bwd))[_.\d]* = ", hlo))


_NOT_OPS = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
            "call", "conditional"}


def _pool_sized_ops(hlo, pool_shape):
    """Instructions of the optimized HLO, fusion bodies aside, whose
    result has the element count of the pool or of one layer of it and
    is not the in-place scatter (or its fusion) or a Mosaic call."""
    sizes = {math.prod(pool_shape), math.prod(pool_shape[1:])}
    bodies, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if m:
            cur = bodies.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    called = lambda line: re.search(r"calls=%([\w.\-]+)", line).group(1)
    fusion_bodies = {called(l) for ls in bodies.values() for l in ls
                     if " fusion(" in l}
    found = []
    for comp, lines in bodies.items():
        if comp in fusion_bodies:
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) "
                         r"([a-z][\w\-]*)\(", line)
            if not m or m.group(3) in _NOT_OPS:
                continue
            name, result, op = m.groups()
            counts = {math.prod(int(d) for d in dims.split(",") if d)
                      for dims in re.findall(r"[a-z]\w*\[([\d,]*)\]", result)}
            if not counts & sizes:
                continue
            if op == "scatter" or "tpu_custom_call" in line or (
                    op == "fusion" and any(" scatter(" in l
                                           for l in bodies[called(line)])):
                continue
            found.append(f"{name} = {result.split('{')[0]} {op}")
    return found


def _pool_scatters(hlo, pool_shape):
    """Scatters anywhere in the HLO, fusion bodies included, whose result
    has the pool's shape: the row writers (``kernels._scatter_rows``),
    which :func:`_pool_sized_ops` lets pass."""
    dims = ",".join(map(str, pool_shape))
    return re.findall(rf"%([\w.\-]+) = \w+\[{dims}\]\S* scatter\(", hlo)


def _shaped_like(hlo, *dims):
    """Results anywhere in the HLO, fusion bodies included, with the
    element count of ``dims`` and their last dim (a weight stack can
    share the count, never the head dim)."""
    want = math.prod(dims)
    return sorted({f"{t}[{d}]" for t, d in
                   re.findall(r"\b([a-z]\w*)\[([\d,]+)\]", hlo)
                   if math.prod(int(x) for x in d.split(",")) == want
                   and d.endswith(f",{dims[-1]}")})


def _blocked_chunk_reader(hlo, table_rows=None):
    """A chunk program's attention over K/V pages runs in the blocked
    Mosaic reader, by name; and, over a table of ``table_rows`` keys,
    no f32 value has that count as a dimension: the gathered reader's
    scores (and its gathered K and V) are gone from the program."""
    assert re.search(r"%dstpu_paged_chunk_v2[\w.]* = .*tpu_custom_call", hlo)
    if table_rows:
        assert not re.search(
            rf"f32\[(?:[0-9]+,)*{table_rows}(?:,[0-9]+)*\]", hlo)


def _top_level_results(hlo, dims):
    """(name, opcode, called computation's lines) of the instructions,
    fusion bodies aside, one of whose results has exactly ``dims``."""
    bodies, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if m:
            cur = bodies.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    called = lambda line: re.search(r"calls=%([\w.\-]+)", line)
    fused = {called(l).group(1) for ls in bodies.values() for l in ls
             if " fusion(" in l}
    want = "[" + ",".join(map(str, dims)) + "]"
    found = []
    for comp, lines in bodies.items():
        if comp in fused:
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) "
                         r"([a-z][\w\-]*)\(", line)
            if m and m.group(3) not in _NOT_OPS and want in m.group(2):
                body = bodies.get(called(line).group(1), []) \
                    if called(line) else []
                found.append((m.group(1), m.group(3), body))
    return found


def _state_stepped_in_place(hlo, state_shape, program, unrolled_lead=False):
    """The whole state is only ever the carried buffer, and one layer of
    it is never a value of its own.  A chunk program updates its slot's
    rows in place (a dynamic-update-slice, or the fusion that ends in
    one); a decode program hands the buffer to ``dstpu_state_step``,
    whose result aliases it, and nothing else of the state's shape is
    computed: no copy of it, no slice of a layer, no reduction fusion
    that reads one."""
    results = _top_level_results(hlo, state_shape)
    if program == "decode":
        assert results and all(
            op == "custom-call" and name.startswith("dstpu_state_step")
            for name, op, _ in results), [(n, o) for n, o, _ in results]
        aliased = re.findall(
            r"%(dstpu_state_step[\w.]*) = .*?custom-call\((.*?)\), "
            r"custom_call_target=\"tpu_custom_call\".*?"
            r"output_to_operand_aliasing=\{\{1\}: \((\d+), \{\}\)\}", hlo)
        assert len(aliased) == len(results)
        shaped = "f32[" + ",".join(map(str, state_shape)) + "]"
        for _, operands, at in aliased:
            # the aliased operand is the carried buffer itself: a loop's
            # tuple element, or (``unrolled_lead``) the entry's own
            # parameter where a leading stack's loop of one layer was
            # unrolled
            operand = operands.split(", ")[int(at)].split("*/")[-1]
            carried = "(get-tuple-element|parameter)" if unrolled_lead \
                else "get-tuple-element"
            assert re.search(
                re.escape(operand) + r" = " + re.escape(shaped)
                + r"\S* " + carried + r"\(", hlo), operand
        # and no fusion takes the buffer (to slice a layer out and reduce
        # it, as the parent's two a layer did): the entry's own parameter
        # aside, it is only ever a loop's tuple element
        assert re.findall(r"%(?!cache)[\w.\-]+ = " + re.escape(shaped)
                          + r"\S* parameter\(", hlo) == []
    else:
        for name, op, body in results:
            assert op == "dynamic-update-slice" or (
                op == "fusion" and any(
                    "ROOT" in l and " dynamic-update-slice(" in l
                    for l in body)), (name, op)
    assert _top_level_results(hlo, state_shape[1:]) == []
    assert _top_level_results(hlo, (1,) + state_shape[1:]) == []
    # and it is updated once a layer, never rematerialised: with the
    # three linear layers of a period unrolled in one loop body the
    # compiler recomputed a layer's in-place update from the buffer it
    # had already overwritten, under a full chip's memory pressure only,
    # and the state moved twice a step (v5e, PR 35)
    assert "remat" not in " ".join(name for name, _, _ in results)
