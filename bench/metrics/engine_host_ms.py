"""Host time per engine iteration outside the Program calls it makes."""


def read(record):
    its = [it for it in record.iterations if it.calls]
    if not its:
        return None
    own = sum((it.t1 - it.t0) - sum(c.t1 - c.t0 for c in it.calls)
              for it in its)
    return 1e3 * own / len(its)
