"""CPU test that what a seed gives the benchmark's ResNet-50 and GoogLeNet
cells stays as it was when each configuration came to name its own
reference module (``perfbench/references``): the weights, the FC weights,
the images, the nonzeros, the operations a forward requires and the
reference's logits."""
import hashlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from perfbench import correct, counts, harness, references, weights  # noqa: E402

# Weights, FC weights, images and nonzeros are compared to the bit; XLA's
# CPU backend sums a convolution in an order that depends on its thread
# count, so the reference's logits are compared to 1e-5 of their row's
# largest.
FROZEN_SEED = 2**32 + 17
FROZEN = {
    "resnet50-224": {
        "weights": "d2dc96f0b998a71a", "fc": "0915b5bc865b615f",
        "images": "ba1859577aa88226", "nnz": "35c9ba927096d346",
        "flops_per_forward": 123186764928.0,
        "row_max": [101.07745361328125, 109.04411315917969,
                    100.76145935058594, 105.16703796386719],
        "logits": [[-5.142168045043945, -62.382286071777344,
                    -26.03557586669922],
                   [-7.03203010559082, -61.2894287109375,
                    -27.957094192504883],
                   [-2.414134979248047, -62.32404708862305,
                    -25.567523956298828],
                   [0.9189438819885254, -65.31295013427734,
                    -29.54929542541504]]},
    "googlenet-224": {
        "weights": "3e6b8e28ddb04dd7", "fc": "e1c843e5f3eec4b7",
        "images": "ba1859577aa88226", "nnz": "e65fb0e19c953ffd",
        "flops_per_forward": 43807903424.0,
        "row_max": [0.9636833071708679, 1.0164096355438232,
                    0.9369946718215942, 0.9437118172645569],
        "logits": [[-0.1365937441587448, -0.18669246137142181,
                    0.6364485025405884],
                   [-0.19651426374912262, -0.181545227766037,
                    0.6955875158309937],
                   [-0.12551183998584747, -0.16476976871490479,
                    0.6309136748313904],
                   [-0.1314443200826645, -0.22447548806667328,
                    0.6155205368995667]]},
}


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(FROZEN))
def test_seeded_inputs_and_reference_are_frozen(name):
    """Weights and FC weights from a seed, the images, the nonzeros, the
    operations a forward of 32 requires, and the reference's logits for 4
    images at 32 px."""
    cfg = harness.load_json(os.path.join(ROOT, "perfbench", "configs",
                                         name + ".json"))
    want = FROZEN[name]
    ref = references.load(cfg)
    w = ref.make_weights(cfg, FROZEN_SEED)
    fc_ws = ref.fc_weights(cfg, weights.fc_seed(FROZEN_SEED))
    images = weights.make_images(FROZEN_SEED, 4, 3, 32, 32)
    nnz = counts.nonzeros(w)
    assert _digest(a for k in sorted(w) for a in w[k]) == want["weights"]
    assert _digest(fc_ws) == want["fc"]
    assert _digest([images]) == want["images"]
    assert _digest([np.array([nnz[k] for k in sorted(nnz)])]) == want["nnz"]
    assert counts.forward_flops(cfg, nnz, 32) == want["flops_per_forward"]
    logits = correct.reference_logits(dict(cfg, image=32), w, fc_ws, images,
                                      block=2)
    row_max = np.abs(logits).max(axis=1)
    np.testing.assert_allclose(row_max, want["row_max"], rtol=1e-5)
    assert np.all(np.abs(logits[:, :3] - np.array(want["logits"]))
                  <= 1e-5 * row_max[:, None])
