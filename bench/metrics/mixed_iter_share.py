"""Share of the window's serving iterations that went through
``Program.prefill`` (prefill chunks, decode slots riding along)."""


def read(record):
    its = [it for it in record.iterations if it.calls]
    if not its:
        return None
    mixed = sum(1 for it in its if any(c.kind == "prefill"
                                       for c in it.calls))
    return 100.0 * mixed / len(its)
