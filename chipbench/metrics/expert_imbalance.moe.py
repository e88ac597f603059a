"""Mean rows of the busiest held expert (``expert_rows_max``, the largest
over layers) over the mean rows a held expert takes in a layer (mean
``expert_rows`` over the held experts summed over layers): 1 where every
held expert takes as many rows. A program whose records lack the counters
gives no value."""
from expert_counts import mean_counter


def read(run):
    rows = mean_counter(run, "expert_rows")
    busiest = mean_counter(run, "expert_rows_max")
    if not rows or busiest is None:
        return None
    return busiest / (rows / run.counts["expert_groups"])
