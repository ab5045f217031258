"""journal + replay: median milliseconds from the front door having read a
request (its ``X-Agentainer-Accepted-Ns`` stamp, taken before the journal
write) to the engine's ``/chat`` handler seeing it: journal write,
``mark_processing``, replica choice, connect, send. The engines' newest
samples at the window's end; one machine, one wall clock."""

from harness import counters


def read(before, after, responses, trace, cell):
    return counters.recent_median(after, "journal_dispatch_ms_samples")
