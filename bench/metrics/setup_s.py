"""setup_s: seconds from the process's start to the window's opening:
imports, making the weights on the device, building the engine, and
compiling or loading from the persistent cache every program the cell's
traffic reaches (warm-up serves one request per prefill bucket)."""


def read(rec):
    return rec.setup_s
