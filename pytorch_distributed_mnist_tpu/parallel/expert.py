"""Expert parallelism: sharding rules for MoE expert weights.

SURVEY.md section 2c marks EP ABSENT in the reference; here it is one more
``PartitionSpec`` table over the same machinery as tensor parallelism
(``parallel/tensor.py``): expert weights carry a leading ``num_experts``
dim, the rules shard it on the ``expert`` mesh axis, and the MoE combine
einsum's sum over experts (``models/moe.py``) becomes XLA's AllReduce over
that axis — every device computes only its local experts, which is the
whole point of EP.

The table covers both expert layers of ``models/moe.py``: ``SwitchMoE``
(``w1, b1, w2, b2``) and the decoder's ``SparseExperts`` (``w_gate, w_up,
w_down``; its router and shared expert stay replicated). For the second
the rules say where each chip's ``experts_held`` range lives; the exchange
of tokens between the chips (the all-to-all) is not written yet: one chip
runs its share alone (``benchmark`` cell ``train_laguna_ep8_8k``), and the
whole layer under an ``expert`` axis is ROADMAP's next step.

Composes with DP the same way TP does: merge the rule dicts and build a
``('data', 'expert')`` mesh.
"""

from __future__ import annotations

from typing import Dict, Tuple

from jax.sharding import PartitionSpec as P


def moe_ep_rules(axis: str = "expert") -> Dict[Tuple[str, str], P]:
    """Path-suffix rules (see ``parallel.tensor.leaf_spec``) for the
    expert weights of ``SwitchMoE`` and ``SparseExperts``.

    The router stays replicated — every device must route identically for
    the combine to agree.
    """
    return {
        ("moe", "w1"): P(axis, None, None),
        ("moe", "b1"): P(axis, None),
        ("moe", "w2"): P(axis, None, None),
        ("moe", "b2"): P(axis, None),
        ("moe", "w_gate"): P(axis, None, None),
        ("moe", "w_up"): P(axis, None, None),
        ("moe", "w_down"): P(axis, None, None),
    }
