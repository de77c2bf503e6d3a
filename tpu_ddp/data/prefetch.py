"""Device prefetch: keep the next batches' host->device transfers in flight.

The reference overlaps host work with compute through DataLoader workers +
``pin_memory=True`` (reference part1/main.py:36-41). The TPU-native
equivalent is to issue ``device_put`` for upcoming batches before the
current step completes — JAX transfers are asynchronous, so a small
lookahead hides the PCIe latency behind the device step.
"""

from __future__ import annotations

import collections
from typing import Callable, Iterable, Iterator


def prefetch_to_device(batches: Iterable, put_fn: Callable, depth: int = 2
                       ) -> Iterator:
    """Yield ``put_fn(batch)`` results with ``depth`` transfers in flight.

    ``put_fn`` is typically ``Trainer.put_batch`` applied to the loader's
    ``(images, labels)`` tuples; ``depth=0`` degenerates to plain mapping
    (no lookahead). A negative depth raises — it would silently become
    the no-lookahead mapping, masking a config typo.

    Composition with the engine's pipelines (round 6):

    - **dispatch pipeline** (``cfg.dispatch_depth``, train/pipeline.py):
      orthogonal and complementary. Prefetch overlaps host->device
      TRANSFERS with compute; the dispatch window overlaps host-side
      RESULT HARVESTING with compute. The epoch loop runs both —
      transfers of batch i+depth are in flight while step i executes
      and step i-dispatch_depth's loss is being accounted.
    - **fault injection** (resilience/chaos.py): only faults that
      poison a batch host-side on an exact step (``nan-grad``) disable
      prefetch — the poisoning must happen before the transfer.
      Passive injectors (slow-rank, hard-exit, corrupt-ckpt,
      stalled-step) compose with it (``FaultInjector.poisons_batches``).
    - **grouped dispatch** (``cfg.steps_per_dispatch > 1``): not
      composed; the grouped loop stages K batches per call via
      ``put_batches`` instead.
    """
    if depth < 0:
        raise ValueError(f"prefetch depth must be >= 0, got {depth} "
                         "(0 = no lookahead)")
    if depth == 0:
        for b in batches:
            yield put_fn(*b) if isinstance(b, tuple) else put_fn(b)
        return
    it = iter(batches)
    queue = collections.deque()
    try:
        while len(queue) < depth:
            b = next(it)
            queue.append(put_fn(*b) if isinstance(b, tuple) else put_fn(b))
    except StopIteration:
        pass
    while queue:
        yield queue.popleft()
        try:
            b = next(it)
            queue.append(put_fn(*b) if isinstance(b, tuple) else put_fn(b))
        except StopIteration:
            continue
