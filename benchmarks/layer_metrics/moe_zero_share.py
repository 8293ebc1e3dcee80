"""The share of a decode round's routing choices that fell on zero-compute
(identity) slots: ``moe/assignments_zero`` (``transformer/
moe_dropless.py``, one counter event an expert layer a decode round, over
the round's active rows) over the rows the round was dispatched with
(``n_active`` of its ``serve/decode_step`` span) x the router's top-k x the
expert layers; the median of the window's decode rounds. With 256 of 768
slots and seeded weights it is about a third: the mechanism engages, and a
token's real experts vary.

The engine counts a round's tokens, and its counters, a round after it
dispatched it: the counters that follow a span belong to an EARLIER one, so
rounds are matched in order of dispatch, and the counters of a round that
was dispatched before the window opened are left out."""

from benchmarks.harness.stats import median


def compute(run):
    moe = (run["program"].info or {}).get("moe") or {}
    top_k = moe.get("top_k")
    if not top_k:
        return None
    dispatched, rounds = [], []
    for e in run.get("window_events") or []:
        if e.get("name") == "serve/decode_step" \
                and e.get("kind") == "span_start":
            dispatched.append(e.get("n_active"))
        elif e.get("name") == "moe/assignments_zero" \
                and e.get("kind") == "counter":
            if e.get("layer", 0) == 0:
                rounds.append([dispatched.pop(0), []] if dispatched
                              else None)
            if rounds and rounds[-1] is not None:
                rounds[-1][1].append(float(e["value"]))
    shares = [100.0 * sum(zero) / (rows * top_k * len(zero))
              for rows, zero in filter(None, rounds) if rows]
    return median(shares) if shares else None
