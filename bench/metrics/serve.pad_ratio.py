"""Rows computed per row requested: (requested + ``BmuEngine.padded``) /
requested, over the traced window."""


def read(ctx):
    c = ctx["counters"]
    if not c["requested"]:
        return None
    return (c["requested"] + c["padded"]) / c["requested"]
