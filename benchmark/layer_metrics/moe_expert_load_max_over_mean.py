"""Tokens of the busiest held expert over the mean of the held experts,
in the expert layer where that ratio is worst, over the window: from the
per-expert token counts the step accumulates on the device
(``unit_stats``), filed by the driver as ``expert_load_max_over_mean``.
1.0 is an even load; the grouped product's time follows the sum, the
all-to-all of a whole deployment would follow the maximum."""


def read(run):
    return run.counters.get("expert_load_max_over_mean")
