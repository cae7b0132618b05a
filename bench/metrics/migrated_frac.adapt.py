"""Share of the elements a step's rebalance moves to another part
(StepStats.migration_totalv over the rebalanced mesh's elements),
averaged over the steps, in percent."""


def read(rec):
    steps = rec.get("steps")
    if not steps:
        return None
    return 100.0 * sum(s["migrated_frac"] for s in steps) / len(steps)
