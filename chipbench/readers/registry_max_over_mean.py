"""The largest over the mean of one labelled counter's series, each as
its growth over the window: the imbalance of a load that the program
counts per expert, per shard or per queue. 1.0 is an even load.

Parameters: ``metric`` (a counter of the program's registry).
"""


def read(ctx: dict, spec: dict):
    name = spec["metric"]
    if name not in ctx["after"]:
        return None

    def by_labels(state):
        return {
            tuple(sorted(s["labels"].items())): s.get("value", 0.0)
            for s in state.get(name, {}).get("series", [])
        }

    before, after = by_labels(ctx["before"]), by_labels(ctx["after"])
    growth = [v - before.get(k, 0.0) for k, v in after.items()]
    if not growth or sum(growth) <= 0:
        return None
    return max(growth) / (sum(growth) / len(growth))
