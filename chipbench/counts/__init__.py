"""Operations and bytes a kernel's or a program's ALGORITHM needs, from
the configuration's shapes and what a run observed, one module a count:
``count(config, obs) -> float``. Found by name (a metric file's
``args["count"]``), so a new kernel brings a file here and edits none.

Recomputed operations and re-read bytes do not count: a share of a peak
computed from these can only fall when a kernel recomputes, never pass
100%. Each module carries its ``SELFTEST_CASE``: (config, obs, answer).
"""
