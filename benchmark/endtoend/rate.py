"""Units answered inside the window over its seconds (``stats.rate``)."""

import stats


def value(how: dict, records, seconds: float) -> float:
    return stats.rate(records, seconds)
