"""Closed-loop traffic over a fixed set of message sizes.

One caller sends its next call as soon as the previous one returns. The
sizes come in blocks: each block holds every size of the mix
`per_block` times, in an order drawn from the seed. So every seed sends
the same sizes in the same proportions and only the order differs.

Parameters (the traffic file): `sizes_bytes`, a list of message sizes
per rank; `per_block`, how often each size appears in a block (default
1).
"""

from __future__ import annotations

import numpy as np


class Traffic:
    def __init__(self, params: dict, seed: int):
        self.sizes = [int(s) for s in params["sizes_bytes"]]
        self._block = np.repeat(self.sizes, int(params.get("per_block", 1)))
        self._rng = np.random.default_rng(seed)
        self._queue: list[int] = []

    def next_call(self) -> tuple[int, float | None]:
        """The next call's size in bytes per rank, and when it is due
        (seconds into the window; None in a closed loop: at once)."""
        if not self._queue:
            self._queue = self._rng.permutation(self._block).tolist()[::-1]
        return self._queue.pop(), None
