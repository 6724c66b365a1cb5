"""Most of the window class's KV blocks in use at once since warm-up
(``sched.window_blocks_peak``, read after every ``step()`` by the
scheduler's own accounting) over that class's blocks: ``max_num_seqs`` rows
of the pages a window reaches. Under 100 because a row holds its ninth
page only while its window straddles a page boundary."""

UNIT, SOURCE = "%", "program_counter"


def read(rec):
    hybrid = rec.get("hybrid")
    if not hybrid or not hybrid.get("window_blocks_total"):
        return None
    return (100.0 * hybrid["window_blocks_peak"]
            / hybrid["window_blocks_total"])
