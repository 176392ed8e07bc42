"""The joins' own host-side work per query in the traced slice, in
milliseconds: the sum of the program's ``join.build`` spans (one a reduce
group, or one a broadcast: pull the build side's pieces, dispatch what
folds them), ``join.probe`` spans (one a probe group against its build: the
probe and condition / expansion launches and their host syncs, which wait
for what the device has queued) and ``join.decide`` spans (an adaptive
join's materialisation and count of its build side and the building of its
inner plan) over the queries completed.  The children's compute is in none
of them: a span closes before the join pulls from a child.  ``SPANS`` names
all three, so the device's idle gaps under them get their names.  None where
the program has no such span."""
from benchmark.span_sums import ms_per_query

SPANS = ("join.build", "join.probe", "join.decide")


def read(ctx):
    return ms_per_query(ctx, *SPANS)
