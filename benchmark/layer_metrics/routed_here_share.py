"""Per-layer metric ``routed_here_share.*`` (PR 33): of the live tokens'
choices of an expert, the share that fell on an expert this chip holds
(the mean over the slice's decode chunks of the program's own count, from
the chunks' ``engine.emit`` spans: ``benchmark/experts.py``). A quarter
where a chip holds a quarter of the experts and the routing is even. A
program that holds every expert it routes over counts none: None."""

from benchmark import experts, program_spans


def read(run):
    share = experts.chunk_stat_mean(program_spans.engine_spans(),
                                    "routed_here_share")
    return None if share is None else 100.0 * share
