"""cache manager: milliseconds one restore of a session's slot took on the
engine's worker, over the window: the difference of ``total_s`` over the
difference of ``n`` of the ``engine.restore`` phase span (``phases`` of the
engines' ``/metrics``, ``utils/spans.py``). A restore of this family writes a
lane's K/V rows up to its position, its recurrent state and its conv state
(the span carries each leaf's bytes while a capture runs). ``None`` where no
restore happened in the window, or the program records no such span.

Without an entry in ``BENCHMARK.json``: a metric has to be in the line of every
traced run of the cells it lists, and in ``olmo-hybrid.sessions`` no session is
ever restored (``state_restores_per_req`` 0.0 in every run: PERF.md section 6),
so there is no time to divide. A cell in which sessions do come back from the
store lists it."""

from harness import phases

SPAN = "engine.restore"


def read(before, after, responses, trace, cell):
    if SPAN not in phases.names(after):
        return None
    n = phases.delta(before, after, SPAN, "n")
    if n <= 0:
        return None
    return 1000.0 * phases.delta(before, after, SPAN, "total_s") / n
