"""Syncs: all-reduces one call of the compiled decode program executes
(each site times the trip counts of the loops around it), read from its
HLO.  Cells with more than one chip."""


def read(run):
    return run.allreduces_per_decode
