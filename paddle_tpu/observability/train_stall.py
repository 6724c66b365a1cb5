"""Training hot-path stall accounting.

The zero-stall train loop removes three serial seams — host->device input
transfer, per-step host syncs on the loss, and ZeRO-3 offload param fetches —
and each removal is *proved* by a metric here rather than asserted in a
docstring:

- ``train_input_stall_seconds``: wall time the training loop spent WAITING
  for its next device-resident batch (a ``DevicePrefetcher`` queue pop, or
  the inline fetch+transfer when prefetch is off). With prefetch overlapping
  H2D against compute this collapses toward zero.
- ``train_sync_stall_seconds``: wall time spent blocking on device results
  (reading a ``NonBlockingStepResult``'s loss, or the eager per-step
  ``.numpy()`` sync). A dispatch-ahead loop pays this once per log window,
  not once per step.
- ``offload_fetch_overlap_ratio``: fraction of ZeRO-3 host-offload param
  fetch groups whose transfer was dispatched BEFORE the layer that needs
  them ran — i.e. hidden behind the previous layer's compute.
- ``train_donated_input_copies_total``: donation alias-safety audit events —
  a batch leaf aliased an already-donated buffer and was defensively copied
  instead of faulting XLA's no-double-donation rule.

All live in the process-wide default registry, so ``Profiler.export_report``
reads them with no extra plumbing.
"""

from __future__ import annotations

from paddle_tpu.observability.metrics import Counter, Gauge, get_registry

_INPUT_STALL = "train_input_stall_seconds"
_SYNC_STALL = "train_sync_stall_seconds"
_OVERLAP_RATIO = "offload_fetch_overlap_ratio"
_DONATION_COPIES = "train_donated_input_copies_total"
_PREFETCHED = "train_prefetched_batches_total"


def input_stall_counter() -> Counter:
    return get_registry().counter(
        _INPUT_STALL, "seconds the train loop waited for its next batch",
        unit="s")


def sync_stall_counter() -> Counter:
    return get_registry().counter(
        _SYNC_STALL, "seconds the train loop blocked reading device results",
        unit="s")


def offload_overlap_gauge() -> Gauge:
    return get_registry().gauge(
        _OVERLAP_RATIO,
        "fraction of ZeRO-3 offload fetches dispatched ahead of their layer")


def donation_copy_counter() -> Counter:
    return get_registry().counter(
        _DONATION_COPIES,
        "donated-input batch leaves copied by the alias-safety audit")


def prefetched_batches_counter() -> Counter:
    return get_registry().counter(
        _PREFETCHED, "batches moved to device by a DevicePrefetcher")


def record_input_stall(seconds: float):
    input_stall_counter().inc(max(float(seconds), 0.0))


def record_sync_stall(seconds: float):
    sync_stall_counter().inc(max(float(seconds), 0.0))


def set_offload_overlap_ratio(ratio: float):
    offload_overlap_gauge().set(float(ratio))


def stall_snapshot() -> dict:
    """The stall breakdown as one plain dict.

    Registers the metrics on first read so a snapshot taken before any
    training reports explicit zeros rather than missing keys."""
    return {
        _INPUT_STALL: input_stall_counter().value,
        _SYNC_STALL: sync_stall_counter().value,
        _OVERLAP_RATIO: offload_overlap_gauge().value,
        _DONATION_COPIES: donation_copy_counter().value,
        _PREFETCHED: prefetched_batches_counter().value,
    }
