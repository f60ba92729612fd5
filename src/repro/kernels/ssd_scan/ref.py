"""Oracle for the SSD scan kernel (= the model-side chunked SSD)."""
from __future__ import annotations

from repro.models.mamba2 import ssd_chunked


def ssd_ref(x, dt, A_log, Bm, Cm, chunk):
    """x: (b, s, h, p); dt: (b, s, h) (softplus applied); A_log: (h,);
    Bm/Cm: (b, s, g, n). Returns (y, final_state)."""
    return ssd_chunked(x, dt, A_log, Bm, Cm, chunk)
