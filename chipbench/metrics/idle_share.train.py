"""Share of the traced training window, averaged over the chips, in which no
operation ran on a chip: the host's batch, dispatch and loss read-back."""


def read(record):
    reduced = record.get("reduced")
    return None if reduced is None else 100.0 * reduced["idle_share"]
