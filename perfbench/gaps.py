"""What a check reads from any reference: the gaps between the reference's
best logit and each served token's, summarised over the tokens whose
routing is not at a near-tie."""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def summary(gaps: List[np.ndarray], margins: List[np.ndarray],
            min_margin: float, wide: float) -> Dict[str, float]:
    """Over the tokens whose routing margin is at least ``min_margin`` (a
    smaller margin is a routing near-tie: rounding may pick either expert,
    and the token's logits change wholesale): the mean gap, the widest, and
    how many gaps exceed ``wide``; the widest over all tokens; how many
    tokens there were and how many were left out."""
    g = np.concatenate(gaps) if gaps else np.zeros((0,))
    m = np.concatenate(margins) if margins else np.zeros((0,))
    keep = m >= min_margin
    kept = g[keep]
    return {"gap_mean": float(kept.mean()) if kept.size else float("nan"),
            "gap_max": float(kept.max()) if kept.size else float("nan"),
            "wide_gaps": int((kept > wide).sum()),
            "gap_max_all": float(g.max()) if g.size else float("nan"),
            "tokens": int(g.size), "near_ties": int((~keep).sum())}
