"""Traffic kind ``train_loop_quant``: ``train_loop``'s closed loop of one
client, a fit and then the new model's evaluation on the resident holdout,
for a configuration that trains under LightGBM's quantized-training parameters
(``use_quantized_grad``, ``num_grad_quant_bins``).

Set-up, window and the planted faults are ``train_loop``'s.  What differs is
the reference ``check`` hands the window's last fit to
(``benchmark/reference_quant.py``: the float reference's five numbers and the
quality of the quantized choice of column), and one more fault: a fit at fewer
levels than the configuration states.
"""

import numpy as np

from benchmark import reference_quant
from benchmark.traffic import train_loop
from benchmark.traffic.train_loop import _train, free, setup, window  # noqa: F401  (the kind's interface)

reference = reference_quant  # prove.py asks the traffic for its reference's VARIANTS

FEWER_BINS = 2


def check(state, result, variant=None):
    """``{name: (value, limit)}`` for the last fit of the window; with a
    ``variant`` (``reference_quant.VARIANTS``) the float reference stands in
    for the program with that fault planted in it."""
    trees = result["booster"]._host_trees()
    scores, loss = result.pop("evaluation")  # the device's copy goes with it
    holdout_scores, holdout_logloss = np.asarray(scores), float(loss)
    del scores, loss
    gaps = reference_quant.compare(
        state["cfg"], state["seed"], trees, state["label_mean"], variant=variant, holdout_scores=holdout_scores,
    )
    limits = state["limits"]
    result["observed"] = {**{k: v for k, v in gaps.items() if k not in limits}, "holdout_logloss": holdout_logloss}
    return {k: (gaps[k], lim) for k, lim in limits.items()}


# ---- planted faults: each must make ``correct`` come out false ------------
def fault_fewer_levels(params, ds):
    """Gradients rounded to ``FEWER_BINS`` levels where the configuration
    states more: the control of ``quant_choice_gap``.  Another program (the
    levels are static), so a compile of its own at size."""
    return _train({**params, "num_grad_quant_bins": FEWER_BINS}, ds)


# each fault is the part of the timed path it stands in for: ``setup``'s keyword
FAULTS = {
    **{k: v for k, v in train_loop.FAULTS.items() if k != "half_batch"},
    "fewer_levels": {"train_fn": fault_fewer_levels},
    "half_batch": train_loop.FAULTS["half_batch"],  # last: it spends the data set
}
