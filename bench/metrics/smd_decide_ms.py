"""smd_decide_ms (ms): the mean time of one SMD keep decision in the data
pipeline over the traced window: the sum of the ``smd_decide_s`` the
window's history entries report over the sum of their ``smd_decisions``
(each chunk's totals, spread over its executed steps)."""


def read(record, trace):
    hist = [h for h in record.get("history", ()) if "smd_decisions" in h]
    decisions = sum(h["smd_decisions"] for h in hist)
    if decisions <= 0:
        return None
    return 1e3 * sum(h["smd_decide_s"] for h in hist) / decisions
