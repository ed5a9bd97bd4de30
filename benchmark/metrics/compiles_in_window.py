"""Programs the backend compiled inside the window (the compile
ledger's delta): should be 0."""


def read(ctx):
    c = ctx["compile"]
    return float(c["compiled"]) if "compiled" in c else None
