"""What set-up is made of: seconds since the process started, by phase.

``run.py`` imports this first, so ``T0`` is as close to the start of the
process as Python code gets; builders and drivers call ``mark`` as each
phase of set-up ends, and the marks are printed with the run's notes on
standard error (PERF.md records them from a cold and a warm run)."""
import time

T0 = time.perf_counter()
MARKS = []


def mark(name: str) -> None:
    MARKS.append([name, time.perf_counter() - T0])


def compiles(before: float) -> dict:
    """The program's own account of its compiles (its ``compile.*``
    spans, while its ring still holds them), as [kind, key, seconds,
    hit | miss | off], apart by whether they began before the clock
    reading ``before`` (the window's opening): what set-up compiled and
    what it found in the persistent cache, and what was left to the
    window and after."""
    try:
        from paddle_tpu.observability import tracing
        spans = tracing.query("compile.*")["spans"]
    except (ImportError, AttributeError):
        return {}
    out = {"in_setup": [], "later": []}
    for s in spans:
        attrs = s.get("attrs", {})
        out["in_setup" if s["t0"] < before else "later"].append(
            [s["name"][len("compile."):], attrs.get("key"),
             round(s["dur"], 2), attrs.get("cache")])
    return out
