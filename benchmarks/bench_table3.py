"""Table III: per-block feature counts and complexity of the paper DNNs,
extracted from our JAX models.

Feature counts match the paper exactly (``features_match=1`` on every row).  For the
"complexity" column the paper counts k^2 * H_out * W_out * C_out (the input
channel factor is missing: B-LeNet block-2 is listed as 0.040 MOPs where the
true conv cost is 5*5*6*16*10*10 = 0.240 M MACs).  We report both our true
MAC counts and the paper's convention to make the discrepancy auditable.
"""
from __future__ import annotations

from typing import List

import numpy as np

from repro.models.branchy import PAPER_MODELS, TABLE_III_FEATURES
from repro.models.cnn_layers import Conv, Residual, Sequential

from .common import Row, kv, timed

#: Table III complexity column (MOPs) for the backbone blocks.
TABLE_III_MOPS = {
    "b-alexnet": [0.043, 6.711, 10.145, 13.523, 29.045],
    "b-resnet": [0.004, 0.021, 0.021, 0.083, 0.664],
    "b-lenet": [0.118, 0.040, 0.048],
}


def _paper_convention_macs(seq: Sequential, in_shape) -> float:
    """k^2 * H_out * W_out * C_out per conv (no input-channel factor)."""
    total = 0.0
    shape = in_shape
    for lyr in seq.layers:
        if isinstance(lyr, Conv):
            oh, ow, oc = lyr.out_shape(shape)
            total += lyr.kernel * lyr.kernel * oh * ow * oc
        elif isinstance(lyr, Residual):
            # two 3x3 convs at the output resolution
            oh, ow, oc = lyr.out_shape(shape)
            total += 2 * 9 * oh * ow * oc
        shape = lyr.out_shape(shape)
    return total


def run() -> List[Row]:
    rows: List[Row] = []
    for name, ctor in PAPER_MODELS.items():
        model = ctor()

        def build_profile():
            return model.extract_profile()

        prof, us = timed(build_profile)
        shape = model.input_shape
        for i, blk in enumerate(model.blocks):
            out_shape = blk.out_shape(shape)
            feats = int(np.prod(out_shape))
            conv_macs = _paper_convention_macs(blk, shape)
            rows.append(Row(
                f"table3/{name}/block{i + 1}", us / model.n_blocks_safe()
                if hasattr(model, "n_blocks_safe") else us / len(model.blocks),
                kv(features=feats,
                   features_paper=TABLE_III_FEATURES[name][i],
                   features_match=int(feats == TABLE_III_FEATURES[name][i]),
                   true_MOPs=prof.block_ops[i] / 1e6,
                   paper_convention_MOPs=conv_macs / 1e6,
                   paper_MOPs=TABLE_III_MOPS[name][i])))
            shape = out_shape
    return rows


if __name__ == "__main__":
    for r in run():
        print(r.csv())
