"""The part of ATP's boundary collectives (collective ops under an
``atp:col[..]`` or ``atp:row[..]`` name scope: ``core/atp.py``'s
``atp_linear`` in every form, ``core/overlap.py``'s rings) in which no
other op runs on that device, over the traced window, averaged over
devices."""
import re

from bench import trace

BOUNDARY = re.compile(r"(^|[/(])atp:(col|row)\[")


def read(r):
    exposed, found = 0.0, False
    for ops in r.trace.ops.values():
        coll = trace.clip([(o.start, o.end) for o in ops
                           if o.collective and BOUNDARY.search(o.scope)],
                          r.window)
        other = trace.clip([(o.start, o.end) for o in ops
                            if not o.collective], r.window)
        found |= bool(coll)
        exposed += trace.length(trace.subtract(coll, other))
    if not found:
        return None
    span = len(r.trace.ops) * (r.window[1] - r.window[0])
    return 100.0 * exposed / span
