"""Kernels: of the paged decode program's device time in the traced part
of the window on the first chip, the share (%) spent in leaf ops under
the name scope `attn` (`models/attention.paged_attend`: page gather,
scores, mask, softmax, weighted sum); the `while` of the layer scan,
which encloses them, is not counted again.  Reads
`run.trace["decode_attn_share"]`, which `bench/lib/spans.scope_share`
computes from the trace and the decode programs' compiled HLO; None
where the trace holds no such reading."""


def read(run):
    return (run.trace or {}).get("decode_attn_share")
