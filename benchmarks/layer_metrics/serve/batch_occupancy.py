"""Mean active rows over decode slots, in percent, over the steps of the
untraced window that decoded (from the engine's step reports)."""


def read(run, observed, reduced):
    active = [a for a in observed["active"] if a]
    if not active:
        return None
    return 100.0 * sum(active) / len(active) / observed["slots"]
