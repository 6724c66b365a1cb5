"""How uneven the router's load on the held experts is: the largest held
expert's pairs in a decode step over the mean held expert's (the step's
pairs to held experts / the experts held), both means over the expert
layers and the decode steps since warm-up, counted inside the compiled
step. 1 is even; the grouped product's row tiles follow the largest."""
from perfbench.harness import hybrid_view

UNIT, SOURCE = "x", "program_counter"


def read(rec):
    pairs = hybrid_view.step_mean(rec, "moe_pairs_held")
    largest = hybrid_view.step_mean(rec, "moe_load_max")
    if not pairs or largest is None:
        return None
    return largest / (pairs / rec["model"]["experts_held"])
