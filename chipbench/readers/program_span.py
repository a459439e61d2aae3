"""A statistic over the spans the PROGRAM recorded about itself.

The program's layers (``paddle_tpu.observability``: engine, trainer,
front door, compile cache) keep their completed spans in a ring on
``time.perf_counter``, the clock of ``chipbench.setup_marks``; this
reader asks the ring's ``query`` for ``args["span"]`` (a name, a prefix
``"compile.*"``, or a list of either) over one phase of the run:

- ``"window"``: the measured window, cut from what a reader can see
  without the driver's help: the last set-up mark (``warm_steps`` /
  ``ramp``, written immediately before the window opens) and
  ``obs["host"]["window_s"]``;
- ``"setup"``: this run's first mark (``imports``) to the window.

``args["field"]`` is ``dur``, ``self`` (the duration minus what child
spans cover) or an attribute; with ``args["over"]`` each span's value is
divided by that attribute. ``args["stat"]``: ``mean``, ``sum``,
``count``, or ``sum_per`` (the sum over the count of ``args["per"]``
spans in the phase: ms of sampling a ``serving.step``), times
``args["scale"]``. None where the program has no ring, no query (a
program from before PR 26) or no such span, and where the driver took
nothing on the host's clock (no window to cut); a driver that gives no
``window_s`` leaves the window open at its end.
"""
import sys

from ..setup_marks import MARKS, T0


def _phase(name: str, host: dict):
    if not MARKS or not host:
        return None
    w0 = T0 + MARKS[-1][1]
    if name == "window":
        return w0, w0 + host.get("window_s", float("inf"))
    if name == "setup":
        return T0 + MARKS[0][1], w0
    raise SystemExit(f"chipbench: program_span: unknown phase {name!r}")


def read(args: dict, obs: dict):
    try:
        from paddle_tpu.observability import tracing
        query = tracing.query
    except (ImportError, AttributeError):
        return None
    phase = _phase(args["phase"], obs.get("host"))
    if phase is None:
        return None
    names = args["span"]
    spans, dropped = [], 0
    for name in [names] if isinstance(names, str) else names:
        got = query(name, *phase)
        spans += got["spans"]
        dropped = got["dropped_total"]
    if dropped:
        print(f"chipbench: program_span: the program's ring dropped "
              f"{dropped} spans; {args['span']} may be incomplete",
              file=sys.stderr)
    field, over = args.get("field", "dur"), args.get("over")
    values = []
    for s in spans:
        v = s[field] if field in ("dur", "self") \
            else s.get("attrs", {}).get(field)
        if over is not None and v is not None:
            d = s.get("attrs", {}).get(over)
            v = v / d if d else None
        if v is not None:
            values.append(v)
    if not values:
        return None
    stat, scale = args["stat"], args.get("scale", 1.0)
    if stat == "count":
        return len(values)
    if stat == "sum":
        return sum(values) * scale
    if stat == "mean":
        return sum(values) / len(values) * scale
    if stat == "sum_per":
        per = len(query(args["per"], *phase)["spans"])
        return sum(values) / per * scale if per else None
    raise SystemExit(f"chipbench: program_span: unknown stat {stat!r}")


# its cases with numbers need the program's ring on a fake clock and are
# tests/test_program_spans.py's (eight of them); here only that a span
# nobody recorded reads nothing
SELFTEST_CASE = ({"span": "no.such.span", "phase": "window",
                  "stat": "count"}, {}, None)
