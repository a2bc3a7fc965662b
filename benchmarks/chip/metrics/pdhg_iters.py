"""PDHG iterations per control interval, summed over phases and domains, as
the system's step returns them; mean over the window."""


def read(run):
    if not run.pdhg_iters:
        return None
    return sum(run.pdhg_iters) / len(run.pdhg_iters)
