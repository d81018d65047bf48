"""(token, choice) pairs that named a held expert and were not served, in
the whole window (program counter, ``routing_log``). A pair is served
where the row the combine reads for it lies in its own expert's group of
the grouped matmuls and was filled from its own token
(``parallel/moe_dispatch._served``). The layer drops none, and a run that
counts one is not ``correct``. Layer: Step."""


def read(run):
    routing = run.counters.get("routing") or {}
    return routing.get("dropped")
