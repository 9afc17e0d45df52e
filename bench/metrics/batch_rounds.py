"""Rounds per batch job (`CountDistResult.rounds`, counted by the round
driver `runtime.run_staged`), averaged over the window's jobs."""


def read(r):
    rounds = r.counters.get("rounds")
    return sum(rounds) / len(rounds) if rounds else None
