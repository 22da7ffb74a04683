#!/usr/bin/env python3
"""A traced step by the scopes you name, for PERF.md's section 5 where
``scope_reduce``'s own report does not know a cell's scopes:

    python3 -m benchmark.scope_table <trace.xplane.pb> [--top N] <scope> ...

For each scope the device time a step under it, forward and backward (the
backward holds a rematerialized layer's second forward); then the time
under ``forward`` and none of the scopes named; then, with ``--top``, the
N longest instructions under each scope and under none. Everything is
``scope_reduce``'s reading of the trace (``scoped_events``, ``segments``,
``classify``, ``per_step_ms``); this file only chooses what to print.
The hybrid cell's table: ``embed attention mamba mamba_in_proj mamba_conv
ssd mamba_gate_norm mamba_out_proj mlp head loss flash_fwd flash_dq
flash_dkv``."""
import argparse

from benchmark import scope_reduce as sr, trace_reduce

NO_SCOPE = "under forward, none of the scopes"


def _under(scope, scopes, path):
    if scope == NO_SCOPE:
        return (sr.classify(path) in (sr.FORWARD, sr.BACKWARD)
                and set(scopes).isdisjoint(sr.segments(path)))
    return scope in sr.segments(path)


def rows(ctx, scopes):
    """[(scope, forward ms, backward ms)] a step for each of ``scopes``
    and, last, for what runs under ``forward`` and none of them."""
    return [(scope,) + tuple(
        sr.per_step_ms(ctx, lambda n, p: _under(scope, scopes, p)
                       and sr.classify(p) == direction) or 0.0
        for direction in (sr.FORWARD, sr.BACKWARD))
        for scope in tuple(scopes) + (NO_SCOPE,)]


def table(ctx, scopes, top=0):
    """The lines described above, of a context whose scoped events are
    read (``scope_reduce.scoped_events``)."""
    events = sr.scoped_events(ctx)
    if not events:
        return ["no device plane, or no event under a scope"]
    steps = len(trace_reduce.step_events(ctx.lines))
    out = [f"{steps} steps, step_device_ms {ctx.step_device_ms():.3f}"]
    out += [f"  {scope:34s} forward {fwd:8.3f} backward {bwd:8.3f} sum "
            f"{fwd + bwd:8.3f} ms" for scope, fwd, bwd in rows(ctx, scopes)]
    for scope in (tuple(scopes) + (NO_SCOPE,)) if top else ():
        longest = {}
        for name, start, dur, p in events:
            if _under(scope, scopes, p):
                key = (trace_reduce.shorten(name)[:110], sr.classify(p))
                longest[key] = longest.get(key, 0.0) + dur
        out.append(f"-- longest, {scope}")
        for (name, direction), ns in sorted(longest.items(),
                                            key=lambda kv: -kv[1])[:top]:
            out.append(f"   {ns / steps / 1e6:8.3f} ms {direction:8s} {name}")
    return out


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace")
    parser.add_argument("scopes", nargs="+")
    parser.add_argument("--top", type=int, default=0)
    args = parser.parse_args()
    context = trace_reduce.Context(
        trace=trace_reduce.load(args.trace), chips=1, steps=None,
        dispatch_s=None, job=None, peaks=None)
    sr.scoped_events(context, args.trace)
    print("\n".join(table(context, args.scopes, args.top)))
