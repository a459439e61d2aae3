"""What set-up is made of: seconds since the process started, by phase.

``run.py`` imports this first, so ``T0`` is as close to the start of the
process as Python code gets; builders and drivers call ``mark`` as each
phase of set-up ends, and the marks are printed with the run's notes on
standard error (PERF.md records them from a cold and a warm run)."""
import time

T0 = time.perf_counter()
MARKS = []


def mark(name: str) -> None:
    MARKS.append([name, round(time.perf_counter() - T0, 3)])
