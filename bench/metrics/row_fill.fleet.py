"""Percent of the padded hall rows that belong to a built hall in the
traced fleet call: `rows_built` on `repro.sweep.finalize` over `rows` on
`repro.sweep.prepare`."""
from bench import program_spans


def read(ctx):
    return program_spans.count_ratio(ctx, "repro.sweep",
                                     ("repro.sweep.finalize", "rows_built"),
                                     ("repro.sweep.prepare", "rows"))
