"""Per-layer metric ``ssm_step_share`` (PR 62): of the decode programs'
device time, the part under ``ssm_step`` (``ops/ssm.py:ssm_state_step``:
one token's update of the slots' recurrent state, kernel or plain), by
the program's own names (``benchmark/decode_scopes.py``): the state's
update apart from the mixer's projections, convolution and norm, which
``ssm_mixer_share`` counts with it by their shapes. None for a family
with no recurrent layer (no ``ssm_op``), with no recorded map, under
``inside.MIN_SAMPLES`` decode runs, or where over a tenth of the decode
runs' own time is unnamed or unjoined."""

from benchmark import decode_scopes, systems

SCOPES = ("ssm_step",)


def read(run):
    if getattr(systems.family(run.config), "ssm_op", None) is None:
        return None
    return decode_scopes.decode_share(run.trace, SCOPES)
