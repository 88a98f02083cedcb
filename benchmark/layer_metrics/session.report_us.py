"""Mean time inside ``session.report`` in the worker, publish to the
driver included, over every report of the run (warm-up, window, traced
steps and the loop's last report, which carries the whole result).  The
call under which the device's longest idle gaps fall.  From
``Result.metrics["_spans"]``."""


def read(run):
    spans = run["worker"].get("_spans") or {}
    report = spans.get("session.report")
    if not report or not report["count"]:
        return None
    return 1e6 * report["total_s"] / report["count"]
