"""The comparison that decides ``correct``: every answer of the window
against the plain reference's answer for the same Parquet files.

Numbers compared, each with a limit of its own (the traffic file's
``limits``, set from the readings PERF.md gives):

- ``max_rel_err``: over all float columns of all answers, the largest
  |got - ref| / |ref|;
- ``exact_mismatches``: values of non-float columns (keys, counts, dates,
  integers) that differ, in order; a wrong column set or row count makes
  every value of that answer a mismatch;
- ``failed_queries``: queries of the window that raised, fell back to the
  host, or planned a host operator.
"""
import numpy as np


def answer_gap(got, ref):
    """(largest relative error over float columns, count of exact values
    that differ) of one answer frame against the reference frame."""
    cells = max(ref.shape[0] * ref.shape[1], 1)
    if list(got.columns) != list(ref.columns) or len(got) != len(ref):
        return float("inf"), cells
    worst, wrong = 0.0, 0
    for c in ref.columns:
        g, r = got[c].to_numpy(), ref[c].to_numpy()
        if r.dtype.kind == "f":
            r64 = r.astype(np.float64)
            err = np.abs(g.astype(np.float64) - r64) \
                / np.maximum(np.abs(r64), np.finfo(np.float64).tiny)
            # a NaN on either side is no agreement
            err = np.where(np.isnan(err), np.inf, err)
            worst = max(worst, float(err.max(initial=0.0)))
        elif r.dtype.kind == "M" or g.dtype.kind == "M" \
                or g.dtype == object or r.dtype == object:
            wrong += sum(str(x)[:10] != str(y)[:10] for x, y in zip(g, r))
        else:
            wrong += int((g.astype(np.int64) != r.astype(np.int64)).sum())
    return worst, wrong


def judge(answers, ref, failed_queries: int, limits: dict) -> dict:
    """The numbers compared, each beside its limit, and the verdict.
    ``answers`` are the frames of the queries that returned one."""
    worst, wrong = 0.0, 0
    for got in answers:
        w, x = answer_gap(got, ref)
        worst, wrong = max(worst, w), wrong + x
    compared = {
        "max_rel_err": {"value": worst, "limit": limits["max_rel_err"]},
        "exact_mismatches": {"value": wrong,
                             "limit": limits["exact_mismatches"]},
        "failed_queries": {"value": failed_queries,
                           "limit": limits["failed_queries"]},
    }
    correct = len(answers) > 0 and all(
        v["value"] <= v["limit"] for v in compared.values())
    return {"correct": bool(correct), "answers_compared": len(answers),
            "compared": compared}
