"""(token, expert) pairs routed to the experts held here in one decode
step, a mean over the expert layers and over the decode steps since
warm-up: counted inside the compiled step from the router's own choice and
read with the tokens (``serving.SlotStep``'s telemetry block). All
``max_num_seqs`` rows of the grid route, idle ones too. At 128 rows x 8
choices x 16 / 256 held the uniform expectation is 64."""
from perfbench.harness import hybrid_view

UNIT, SOURCE = "count", "program_counter"


def read(rec):
    return hybrid_view.step_mean(rec, "moe_pairs_held")
