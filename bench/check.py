"""The comparison that decides ``correct``.

Every answer the window produced is compared with the plain reference
(``bench/reference.py``) on the same query:

``u_mismatch``         answers whose u (posting planes read) differs
``cand_cnt_mismatch``  answers whose merged candidate count differs
``id_mismatch``        served ids that are not reference candidates,
                       repeated ids, and answers that serve another
                       number of ids than ``min(keep, candidates)``
``score_err``          widest gap between a served score and the
                       reference's float64 score of that document
``rank_gap``           widest gap by which the reference score of the
                       k-th served document lies below the reference's
                       k-th best, over every k

The first three are exact (limit 0): the rollout, scan, merge and
prune decide them.  The last two carry the L1 ranker's arithmetic; their
limits sit between what the program reads on the chip and what the
control, three-pass ``high`` matmuls, reads (``PERF.md`` gives both).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import numpy as np

from .reference import Answer

NUMBERS = ("u_mismatch", "cand_cnt_mismatch", "id_mismatch", "score_err",
           "rank_gap")


def compare(pairs: Iterable[Tuple[object, Answer]], keep: int
            ) -> Dict[str, float]:
    """``pairs`` of (served response with ``u``, ``cand_cnt``,
    ``doc_ids``, ``scores``; the reference's answer to its query)."""
    out = dict.fromkeys(NUMBERS, 0.0)
    n = 0
    for resp, ref in pairs:
        n += 1
        out["u_mismatch"] += int(resp.u) != ref.u
        out["cand_cnt_mismatch"] += int(resp.cand_cnt) != len(ref.cand)
        ids = np.asarray(resp.doc_ids, np.int64)
        served = ids[ids >= 0]
        ref_score = dict(zip(ref.cand.tolist(), ref.scores.tolist()))
        known = [i for i in served.tolist() if i in ref_score]
        out["id_mismatch"] += (len(served) - len(known)
                               + len(served) - len(set(served.tolist()))
                               + abs(len(served) - min(keep, len(ref.cand))))
        sc = np.asarray(resp.scores, np.float64)[ids >= 0]
        best = np.sort(ref.scores)[::-1]
        for k, (i, s) in enumerate(zip(served.tolist(), sc.tolist())):
            if i in ref_score:
                out["score_err"] = max(out["score_err"], abs(s - ref_score[i]))
                out["rank_gap"] = max(out["rank_gap"],
                                      float(best[k] - ref_score[i]))
    out["n_compared"] = float(n)
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            failed: int) -> Tuple[bool, List[str]]:
    """``correct`` and one line per number compared, ``name value <=
    limit``.  Any request that never got an answer fails the run too."""
    lines = [f"{name} {numbers[name]!r} <= {limits[name]!r}"
             for name in NUMBERS]
    lines.append(f"unanswered {failed} <= 0")
    ok = (failed == 0 and numbers.get("n_compared", 0) > 0
          and all(numbers[name] <= limits[name] for name in NUMBERS))
    return ok, lines
