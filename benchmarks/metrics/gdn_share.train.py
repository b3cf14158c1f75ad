"""Device time under the name scope `gdn` (Gated DeltaNet: its projections,
convolution, the chunked gated delta rule, the gated norm) as a share of the
train step's device time (the XLA module the cell's `trace_modules.step`
names): forward, recomputation, backward."""
from benchmarks import trace_scopes


def read(ctx):
    return trace_scopes.step_share(ctx, "gdn")
