"""Bytes of cache one token takes across the model's layers as the program
allocated them: the scheduler's own gauge ``kv_bytes_per_token`` (the pools'
bytes over the tokens they hold), which ``/metrics`` exposes. The published
latent row is 576 elements a layer; a pool that pads it to whole lane tiles
holds 640."""

UNIT, SOURCE = "B", "program_counter"


def read(rec):
    return (rec.get("latent") or {}).get("kv_bytes_per_token")
