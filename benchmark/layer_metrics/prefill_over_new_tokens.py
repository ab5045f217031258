"""cache manager: tokens the engines prefilled in the window (``prefill_tokens``
of their ``/metrics``) over the NEW tokens of the window's turns as the
generator sent them (the sum of ``want_prompt_tokens`` of the answered
requests, ``harness/loadgen.py``: a first turn's system prompt and task, a
later turn's appended text). 1.0 when every turn prefills only what was
appended (a returning turn also feeds the one token its last reply held out,
so a little over); more when evicted sessions prefill their history again.
The counter runs over the window's seconds and the requests are those due in
it, so the edges differ by the requests the window cut: a few percent."""

from harness import counters


def read(before, after, responses, trace, cell):
    new = sum(r["want_prompt_tokens"] for r in responses if r.get("ok"))
    if new <= 0 or not any("prefill_tokens" in m for m in after):
        return None
    return counters.delta(before, after, "prefill_tokens") / new
