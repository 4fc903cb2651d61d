"""Tokens the server processed inside the window, over the window's seconds:
a request's prompt tokens are credited at the instant its first token arrives
(its prefill is then done), each output token at the instant it arrives. All
the work and all the time of the window: requests that straddle an edge count
for the part that fell inside. Refused and failed requests count nothing."""


def read(obs):
    t0, t1 = obs.window
    tokens = 0
    for r in obs.requests:
        if r.refused or r.state in ("failed", "timed_out") or not r.stamps:
            continue
        if t0 <= r.stamps[0] <= t1:
            tokens += r.prompt_len
        tokens += sum(t0 <= t <= t1 for t in r.stamps)
    return tokens / (t1 - t0) if tokens else None
