"""Model registry: name -> constructor.

The reference constructs its model at a hard-coded call site
(``/root/reference/multi_proc_single_gpu.py:185``); the TPU framework makes
the model a named, pluggable component so the CLI (``--model``) and tests can
select architectures without editing source.
"""

from typing import Callable, Dict

_REGISTRY: Dict[str, Callable] = {}


def register_model(name: str) -> Callable:
    """Class decorator registering a model constructor under ``name``."""

    def wrap(cls):
        if name in _REGISTRY:
            raise ValueError(f"model {name!r} already registered")
        _REGISTRY[name] = cls
        return cls

    return wrap


def _lookup(name: str) -> Callable:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def get_model(name: str, **kwargs):
    """Instantiate a registered model by name."""
    return _lookup(name)(**kwargs)


def list_models():
    return sorted(_REGISTRY)


def model_field_default(name: str, field: str):
    """A registered model's constructor default for ``field`` — the one
    source for flag-level divisibility checks (head/expert counts in the
    training CLI, mesh-size validation messages in serving). Raises
    ``ValueError`` for an unknown model or field, so a typo fails loudly
    instead of reading as "no default"."""
    import dataclasses
    import inspect

    ctor = _lookup(name)
    if dataclasses.is_dataclass(ctor):
        for f in dataclasses.fields(ctor):
            if f.name == field:
                if f.default is not dataclasses.MISSING:
                    return f.default
                if f.default_factory is not dataclasses.MISSING:
                    return f.default_factory()
                break  # required field: no default to report
    else:
        try:
            param = inspect.signature(ctor).parameters[field]
        except (KeyError, TypeError, ValueError):
            pass
        else:
            if param.default is not inspect.Parameter.empty:
                return param.default
    raise ValueError(f"model {name!r} has no field {field!r} with a default")


def model_objective(name: str) -> dict:
    """What weighs the further terms of a registered model's training
    objective where its source states them (the class's ``objective``:
    ``aux_weight``, ``mtp_weight``, ``bias_rate``, the arguments of
    ``train/steps.py _train_step``); ``{}`` for a model that has none."""
    return dict(getattr(_lookup(name), "objective", {}))


def model_accepts(name: str, field: str) -> bool:
    """True if the registered model's constructor takes ``field``.

    Capability probe for CLI flags (e.g. ``--attention`` needs a model
    with an ``attention_fn`` field) — an explicit check, so a genuine
    TypeError from a model constructor is never mistaken for a
    capability mismatch."""
    import dataclasses
    import inspect

    ctor = _lookup(name)
    if dataclasses.is_dataclass(ctor):
        return field in {f.name for f in dataclasses.fields(ctor)}
    try:
        return field in inspect.signature(ctor).parameters
    except (TypeError, ValueError):
        return False
