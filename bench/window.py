"""What one measured window produced, as the drivers hand it back."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Window:
    end_to_end: Dict[str, float]     # the driver's end-to-end quantities
    attempted: int
    failed: int                      # refused, failed or never answered
    wrong_outcome: int               # errors and missing answers
    rows: Dict[str, np.ndarray]      # results of the checked sample
    pool_index: np.ndarray           # (n,) pool row of each checked row
    key_index: np.ndarray            # (n,) key index s of each row
    key_pos: np.ndarray              # (n,) position j within that key
    images: int                      # images answered in the window
    window_s: float
    counters: Optional[dict] = None  # program metrics snapshot
    latency_ms: Optional[np.ndarray] = None   # per request, in due order
    notes: Optional[List[str]] = None


def sample(rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    """``min(n, k)`` distinct indices below ``n``, sorted."""
    return np.sort(rng.choice(n, size=min(n, k), replace=False))
