"""scheduler: prompt tokens sent plus completion tokens asked for, of the
window's answered requests, per second of the window (the generator's
counts). ``req_per_s`` times the tokens of a request: the number to set
beside other systems' tokens per second, not judged because which requests
a window catches moves it by several per cent in the chat mix."""


def read(before, after, responses, trace, cell):
    tokens = sum(r["want_prompt_tokens"] + r["want_completion_tokens"] for r in responses if r.get("ok"))
    return tokens / cell["seconds"] if tokens else None
