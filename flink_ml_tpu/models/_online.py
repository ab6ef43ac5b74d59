"""What the online models share: the published record's arrays, on whichever
side they were born, and the drain of the training loop.

A published record (`_PublishedLR`, `_PublishedKMeans`) holds its arrays as
they were handed in. The unbounded loop hands in device arrays, and they stay
on the device: publishing a version copies nothing and reads nothing back, and
`device_constants()` serves them without a round trip. A host array (a loaded
model, `set_model_data` from a table, a hot swap) is kept as float64 numpy, as
it always was. The host sees a device record only when it asks
(`coefficient`, `centroids`, `get_model_data`, `save`): one accounted readback.
"""

from __future__ import annotations

import weakref
from typing import Iterator, Optional

import numpy as np


def on_device(array) -> bool:
    import jax

    return isinstance(array, jax.Array)


def published(array):
    """An array as a record keeps it: a device array as it is, anything else
    as float64 numpy; None stays None."""
    if array is None or on_device(array):
        return array
    return np.asarray(array, dtype=np.float64)


def on_host(array) -> Optional[np.ndarray]:
    """A record's array for the host, float64: a device array is read back
    through the accounted funnel (`iteration.host_sync.model`, `readback.*`)."""
    if array is None or not on_device(array):
        return array
    from ..utils.packing import packed_device_get

    return packed_device_get(array, sync_kind="model")[0].astype(np.float64)


def kernel_constant(array):
    """A record's array as a float32 kernel constant, on the side it is on."""
    if on_device(array):
        import jax.numpy as jnp

        return array.astype(jnp.float32)
    return np.asarray(array, dtype=np.float32)


def weakly(method):
    """A bound method as the loop's `publish` hook without the loop holding
    its model: the model holds the loop, and a cycle would leave both (and
    the device arrays of the state) to the cycle collector's own time."""
    ref = weakref.WeakMethod(method)

    def call(*args):
        target = ref()
        if target is not None:
            target(*args)

    return call


class OnlineUpdates:
    """The training side of an online model: `set_model_data(stream)` takes a
    stream of (version, state) pairs that `process_updates` publishes one by
    one; the estimator's own unbounded loop publishes each version itself,
    inside its `online.publish` phase (`_follow`), and `process_updates` then
    only drives it. A model says how a state is published
    (`_publish_state`) and under which gauge its version is shown."""

    _updates: Optional[Iterator] = None
    _loop_publishes = False

    def _publish_state(self, version: int, state) -> None:
        raise NotImplementedError

    def _follow(self, updates) -> None:
        """`updates` is an unbounded loop that was given `_publish_state`."""
        self._updates = iter(updates)
        self._loop_publishes = True

    def _take_stream(self, stream) -> None:
        self._updates = iter(stream)
        self._loop_publishes = False

    def process_updates(self, max_batches: Optional[int] = None) -> int:
        """Drain pending training batches, advancing the model version —
        the host-driven analogue of the unbounded feedback loop. ONE atomic
        publication a batch: a concurrent reader sees the old record or the
        new one, never a mixture."""
        from ..utils import metrics

        # the reference's modelDataVersion gauge (OnlineKMeansModel.java:161-166,
        # OnlineLogisticRegressionModel.java:133)
        gauge = type(self).__name__ + ".modelDataVersion"
        metrics.set_gauge(gauge, self.model_version)
        if self._updates is None:
            return self.model_version
        processed = 0
        for version, state in self._updates:
            if not self._loop_publishes:
                self._publish_state(version, state)
            metrics.set_gauge(gauge, version)
            processed += 1
            if max_batches is not None and processed >= max_batches:
                break
        return self.model_version
