"""Reading a compiled step in ways that survive a compiler upgrade.

The printed form of compiled HLO is not a contract: XLA has printed the
element type on an all-reduce's operand and on its result, packs several
reductions into one tuple all-reduce, and embeds source line numbers
(stack-frame tables and per-instruction ``metadata``). These helpers read
what is one: the collective instructions and their result types, the
program without its debug information, and the psums of the jaxpr the
program was compiled from.
"""

import re

_SECTIONS = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def collective_instructions(hlo_text, op="all-reduce"):
    """[(result type text, line)] for every ``op`` instruction
    (``all-reduce``, ``reduce-scatter``; sync or ``-start``). One entry
    per instruction, however many arrays it carries: this is what the
    scheduler can place, so it is the count that says whether separately
    issued reductions stayed separate."""
    pattern = re.compile(
        r"=\s*(?P<result>\(.*?\)|\S+)\s+" + re.escape(op)
        + r"(?:-start)?\(")
    return [(m.group("result"), line.strip())
            for line in hlo_text.splitlines()
            for m in [pattern.search(line)] if m]


def collective_results(hlo_text, op="all-reduce"):
    """(element_type, dims, line) for every array that collective ``op``
    produces: one entry per element of a tuple-shaped (combined)
    instruction. For element types and for what a program reduces in
    all — not for whether reductions stayed separate
    (:func:`collective_instructions`)."""
    return [(t, dims, line)
            for result, line in collective_instructions(hlo_text, op)
            for t, dims in _ARRAY.findall(result)]


def strip_debug_info(hlo_text):
    """The program alone: no stack-frame tables, no ``metadata={...}``."""
    lines, skipping = [], False
    for line in hlo_text.splitlines():
        if line.strip() in _SECTIONS:
            skipping = True
        elif skipping and not line.strip():
            skipping = False
        elif not skipping:
            lines.append(re.sub(r",?\s*metadata=\{[^{}]*\}", "", line))
    return "\n".join(lines)


def find_psums(jaxpr, acc=None):
    """[(body, eqn_index)] for every psum eqn of ``jaxpr``, recursing
    through pjit/shard_map/cond bodies."""
    acc = [] if acc is None else acc
    for i, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name == "psum":
            acc.append((jaxpr, i))
        for v in eqn.params.values():
            for w in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(w, "jaxpr", w)
                if hasattr(sub, "eqns"):
                    find_psums(sub, acc)
    return acc


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for w in (v if isinstance(v, (list, tuple)) else (v,)):
            sub = getattr(w, "jaxpr", w)
            if hasattr(sub, "eqns"):
                yield sub


def flash_calls(jaxpr, acc=None):
    """{kernel: [(operand shapes, result shapes)]} for every
    ``pallas_call`` of the flash kernels in ``jaxpr`` (``flash_fwd``,
    ``flash_bwd``, and ``flash_dq``, ``flash_dkv`` where the two passes
    run: the call's ``name``), recursing through
    every body but a kernel's own. Operands in the order of the call: the
    block offsets, q, k, v, then what the pass takes besides."""
    acc = {} if acc is None else acc
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = str(eqn.params.get("name"))
            if name.startswith("flash_"):
                acc.setdefault(name, []).append(
                    ([tuple(v.aval.shape) for v in eqn.invars],
                     [tuple(v.aval.shape) for v in eqn.outvars]))
            continue
        for sub in _sub_jaxprs(eqn):
            flash_calls(sub, acc)
    return acc


def repeats_and_group_sums(jaxpr, acc=None):
    """The result shape of every ``broadcast_in_dim`` (what ``jnp.repeat``
    is, before its reshape) and the operand shape of every ``reduce_sum``
    in ``jaxpr``, recursing through every body but a Pallas kernel's own."""
    acc = set() if acc is None else acc
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "broadcast_in_dim":
            acc.add(tuple(eqn.outvars[0].aval.shape))
        elif eqn.primitive.name == "reduce_sum":
            acc.add(tuple(eqn.invars[0].aval.shape))
        elif eqn.primitive.name != "pallas_call":
            for sub in _sub_jaxprs(eqn):
                repeats_and_group_sums(sub, acc)
    return acc


def grouped_shapes(B, T, H, Hkv, D):
    """What a repeat of K or V from ``Hkv`` to ``H`` heads is broadcast
    to, and a sum over the groups reduces: in the [B, T, heads, D] layout
    or merged."""
    g = H // Hkv
    return {(B, T, Hkv, g, D), (B * Hkv, g, T, D), (B, Hkv, g, T, D)}


def assert_kv_stay_grouped(jaxpr, B, T, H, Hkv, D):
    """A traced step whose attention has ``H`` query heads over ``Hkv``
    K/V heads: the step's kernels are the forward and the fused backward,
    every one takes K and V as ``[B * Hkv, T, D]``, the backward returns
    dQ at the Q side's shape and dK and dV at that one, and no repeat to
    ``H`` heads and no sum over a group is anywhere. Returns the calls."""
    calls = flash_calls(jaxpr)
    assert set(calls) == {"flash_fwd", "flash_bwd"}, set(calls)
    for name, found in calls.items():
        for operands, results in found:
            assert operands[1] == (B * H, T, D), (name, operands)
            assert operands[2] == operands[3] == (B * Hkv, T, D), (
                name, operands)
            if name == "flash_bwd":
                assert results == [(B * H, T, D)] + [(B * Hkv, T, D)] * 2, \
                    results
    found = grouped_shapes(B, T, H, Hkv, D) & repeats_and_group_sums(jaxpr)
    assert not found, found
    return calls
