"""Device time of the K/V writes into the page pool over the operations'
busy time in the traced rounds: operations whose ``op_name`` lies under
``apx:kv_write`` (``serve/cache.py:write_token``, ``write_prompt``), joined
by ``harness/span_reduce.py``. The write alone: a copy of the pool that XLA
adds after it carries no ``op_name`` and is ``unattributed``; where it is a
remat clone of the write's result (``fusion.N.remat_compressed``), its time
is in ``notes.scope_shares.remat_clones.kv_write`` of the ``trace`` line."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.scope_share(run, "kv_write")
