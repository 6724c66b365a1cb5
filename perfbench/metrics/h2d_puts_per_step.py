"""Host-to-device uploads a decode launch: JAX's host events ``DevicePut*``
inside the ``serving.stage`` spans that lie in a ``serving.decode_step``,
over the number of those ``serving.decode_step`` spans in the traced slice.
Each is one ``to_tensor``; with donation the block table and the positions
go up once for every layer."""
from perfbench.harness import phases

UNIT, SOURCE = "count", "program_span"


def read(rec):
    decode = phases.named(rec, "serving.decode_step")
    if not decode:
        return None
    stages = phases.inside(phases.named(rec, "serving.stage"), decode)
    puts = [x for x in rec["trace"]["spans"] if x[0].startswith("DevicePut")]
    return len(phases.inside(puts, stages)) / len(decode)
