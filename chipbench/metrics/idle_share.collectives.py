"""Share of the traced collective window, averaged over the chips, in which
no operation ran on a chip."""


def read(record):
    reduced = record.get("reduced")
    return None if reduced is None else 100.0 * reduced["idle_share"]
