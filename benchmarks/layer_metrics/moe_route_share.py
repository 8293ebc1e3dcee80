"""What moving tokens to their experts and back costs: device time under
``apx:moe_route`` (scores, group limit, top-k, the layout by expert and the
gather into it) and ``apx:moe_combine`` (the weighted gather back), over
the operations' busy time in the traced rounds."""

from benchmarks.harness import span_reduce


def compute(run):
    route = span_reduce.scope_share(run, "moe_route")
    combine = span_reduce.scope_share(run, "moe_combine")
    if route is None and combine is None:
        return None
    return (route or 0.0) + (combine or 0.0)
