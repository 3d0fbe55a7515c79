"""Tokens generated by the requests the window completed, over the
window's length.  Offered above capacity, the window closes at the first
``step()`` return after ``--seconds`` and leaves its backlog unserved: the
harness stamps a completion only when ``step()`` returns, so a cut inside
the last step would drop that step's completions but keep its time."""


def read(run):
    tokens = sum(int(s.turn.new_tokens) for s in run.served)
    return tokens / (run.window["t1"] - run.window["t0"])
