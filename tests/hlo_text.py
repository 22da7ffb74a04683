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
