"""The ``q`` quantile of all the window's request latencies, in ms; a
failed request counts as slower than any answer (``stats.percentile``)."""

import stats


def value(how: dict, records, seconds: float) -> float:
    latencies = [lat for _, lat, ok, _ in records if ok]
    failed = len(records) - len(latencies)
    return 1e3 * stats.percentile(latencies, failed, float(how["q"]))
