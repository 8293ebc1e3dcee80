"""What the compiled step holds on one chip, from
``compiled.memory_analysis()``: temp + arguments + outputs - aliased."""


def compute(run):
    mem = getattr(run["program"], "memory", None)
    return None if not mem else mem["total_bytes"] / 1e9
