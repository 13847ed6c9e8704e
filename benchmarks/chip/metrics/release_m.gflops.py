"""Mean completions in the released estimates, over the window's answered
requests: the ``m`` the harness reads from each request's decoder, which
the program also observes into its ``serve.release_m`` histogram."""


def read(run):
    ms = [r.m for r in run.answered if r.m is not None]
    return sum(ms) / len(ms) if ms else None
