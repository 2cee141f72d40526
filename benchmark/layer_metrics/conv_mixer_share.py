"""Per-layer metric ``conv_mixer_share`` (PR 67): of the decode programs'
device time, the part under ``short_conv`` (a gated short convolution
whole: its norm, its two projections, the gate, the filter over the
slot's tail and the tail's update), by the program's own names
(``benchmark/decode_scopes.py``): what the layers that keep a recurrent
state of kilobytes cost a decode step beside its attention and its
experts. 0.0 where the maps name nothing so (a family with no such
layer), None with no recorded map, under ``inside.MIN_SAMPLES`` decode
runs, or where over a tenth of the decode runs' own time is unnamed or
unjoined."""

from benchmark import decode_scopes

SCOPES = ("short_conv",)


def read(run):
    return decode_scopes.decode_share(run.trace, SCOPES)
