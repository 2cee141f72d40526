"""Per-layer metric ``unscoped_share`` (PR 57): of the prefill programs'
device time in the slice, and of the decode programs', the part in
operations whose compiled instruction lies under no name of the program's
vocabulary (``ray_tpu/ops/scopes.py``), or that found no instruction in a
recorded map (``benchmark/program_scopes.py``: the join); the LARGER of
the two kinds', so that decode's weight in a slice hides nothing of
prefill. The instrument's own gauge: past a tenth the vocabulary has a
hole, the join lost an executable, or a compile cache handed the run
another tree's executables with that tree's names; the four
``prefill_*_share`` of PR 57 are withheld (None) where the prefill's own
passes a tenth. None with no recorded map, or under
``inside.MIN_SAMPLES`` runs of either kind."""

from benchmark import program_scopes


def read(run):
    return program_scopes.unscoped_share(run.trace)
