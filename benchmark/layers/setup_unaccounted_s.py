"""Seconds of set-up, up to the end of the warming pass, that lie inside
none of the named top-level spans (``startup``, ``init``, ``init_state``,
``reference_check``, ``train_pass``): the data from the seed, the loaders,
``Trainer.__init__``, the state's placement. The guard of set-up's
attribution, as ``unscoped_ms_per_step`` is the step's: ``startup_s +
state_init_s + reference_check_s + warm_pass_s`` and this add up to
``setup_s``. Layer: Entry and compile."""

from benchmark import setup_spans


def read(run):
    return setup_spans.unaccounted(run)
