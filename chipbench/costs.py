"""Least device bytes of the search batches of a window.

A batch must read, once, every row's key (float32, 4 B) and validity (bool,
1 B) on the pages the index selects for the batch's union, and every live
index entry's bitmap (``ceil(H / 32)`` uint32 words), whatever implements
it. The compare work on those bytes runs on the vector units, which have no
published peak, so the share this feeds is a share of HBM bandwidth only.
"""
from __future__ import annotations

KEY_BYTES, VALID_BYTES, WORD_BYTES = 4, 1, 4


def search_least_bytes(selected_pages: int, page_card: int,
                       entries_read: int, resolution: int) -> int:
    """Bytes of ``selected_pages`` pages of ``page_card`` rows and of
    ``entries_read`` entry bitmaps (live entries times batches)."""
    words = -(-resolution // 32)
    return (selected_pages * page_card * (KEY_BYTES + VALID_BYTES)
            + entries_read * words * WORD_BYTES)
