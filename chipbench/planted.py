"""``python3 -m chipbench.planted --workload <serving cell> --seed <n>
--seconds <s> --trace 0``: one run of a serving cell as ``chipbench.run``
makes it, with the fault such a cell can have planted underneath: one
token in two hundred altered where the engine produces it
(``serving_loop.altered_tokens``). It has to print ``"correct": false``:
the widest gap of a served token under the reference's best is the
number that has to catch it. Never a benchmark run; PERF.md keeps the
readings, ``selftest.check_correct_fails`` the small size.
"""
from . import run, serving_loop

if __name__ == "__main__":
    with serving_loop.altered_tokens(every=200):
        run.main()
