"""``python3 -m chipbench.control --workload <cell> --seed <n> --seconds
<s> --trace 0``: one run of a cell as ``chipbench.run`` makes it, with
the CONTROL read beside the run's own numbers and under the same limits.

The control is the plain reference put in the program's place and
computed one precision below the configuration's (int8 for bfloat16). A
comparison is worth its name only if that fails it: this run has to
print ``"correct": false``, its own reading inside the limit and the
control's outside. The benchmark's runs never run it; PERF.md keeps the
readings the limits were set from. At a size a test run can hold it is
``selftest.check_correct_fails``.
"""
from . import run

if __name__ == "__main__":
    run.main(control=True)
