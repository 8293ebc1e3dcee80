"""Share of the collectives of the process's sequence-parallel linear calls
at a tensor axis over 1 that took the ring (``parallel/overlap.py``: the
reduce-scatter hops beside the pieces of its own matmul) and not a blocking
collective of the device, counted while the programs were traced in set-up:
``tp/sp_linear_ring`` and ``tp/sp_linear_blocking`` of
``transformer/tensor_parallel/layers.py``, where every call counts its two
collectives, forward's and its backward's conjugate. 50 = every reduce-scatter
rings and every all-gather is the device's (PR 41: the gather ring lost on the
chip); 0 = a tree that fell back in silence. Whether the ring HIDES anything
is read on the device (``collective_exposed_share``). A program without the
counters (every tree before PR 41, and a tensor axis of 1) reports nothing.

Read beside a device trace only, as every share of a chip's run: the CPU
rehearsal of the four-device cell (``tests/test_rehearsal.py``) lists that
cell's metrics exactly, and a ``perf_opt`` PR may add no name to it."""


def compute(run):
    if run.get("trace") is None:
        return None
    ring = run["counters"].get("tp/sp_linear_ring", 0)
    blocking = run["counters"].get("tp/sp_linear_blocking", 0)
    return 100.0 * ring / (ring + blocking) if ring + blocking else None
