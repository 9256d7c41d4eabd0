"""The LM reference taken a block of sequences at a time: the blocks add
up to the whole batch, and a batch of one block is computed exactly as
before blocks existed."""

import hashlib
import json

import numpy as np
import pytest

from chipbench import harness
from chipbench.reference import decoder_lm
from chipbench.tests.test_check import SEED, tiny_cell

# the tiny LM cell's reference (2 sequences, one block) before the
# reference was blocked, on this CPU (x86-64, jax 0.9.0): each step's
# loss, and digests of the final parameters' bytes and of the first
# gradient's norms
UNBLOCKED = {
    "loss": [6.3088579177856445, 5.996994972229004, 6.077304363250732,
             6.301070213317871, 7.1088643074035645],
    "params_sha256": "6256f242c91315461b9956ab2c20531627656d9f11cec9ce"
                     "df83ec8137294b4f",
    "grad1_sha256": "0dce34e6267faab9ac1fb9102da1dcb1933e4d6f333a479b3"
                    "bcc23f75a6e0e00",
    "raw_grad1_sha256": "b12a4dab4ef934aa6e5e0eb7edaee2c2428864e2bca4ae8"
                        "a22de45eac1413cc3",
}


def _run(cell):
    sess = harness.Session(cell)
    key = harness.seed_key_data(SEED)
    return decoder_lm.run(cell.config, cell.traffic, sess.prog.ref_init(key),
                          sess.ref_pool(key)[:sess.n_check], sess.n_check)


def _sha(obj) -> str:
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()
                          ).hexdigest()


def test_one_block_is_the_unblocked_reference():
    import jax
    ref = _run(tiny_cell("lm"))
    h = hashlib.sha256()
    for x in jax.tree.leaves(ref["params"]):
        h.update(np.asarray(x).tobytes())
    assert ref["loss"] == UNBLOCKED["loss"]
    assert h.hexdigest() == UNBLOCKED["params_sha256"]
    assert _sha(ref["grad1"]) == UNBLOCKED["grad1_sha256"]
    assert _sha(ref["raw_grad1"]) == UNBLOCKED["raw_grad1_sha256"]


def test_blocks_of_two_add_up_to_one_block_of_eight(monkeypatch):
    """8 sequences in 4 blocks against one block, in float32: the blocks'
    sums are the same terms added in another order, a few roundings of
    2**-24 each, so every reading agrees within 1e-6 of its size."""
    import jax
    cell = tiny_cell("lm")
    cell = harness.Cell(**{**cell.__dict__,
                           "traffic": dict(cell.traffic, seqs=8)})
    blocked = _run(cell)
    monkeypatch.setattr(decoder_lm, "ROW_BLOCK", 8)
    whole = _run(cell)
    np.testing.assert_allclose(blocked["loss"], whole["loss"], rtol=1e-6)
    for k in ("grad1", "raw_grad1"):
        for leaf, v in whole[k].items():
            assert blocked[k][leaf] == pytest.approx(v, rel=1e-6), (k, leaf)
    for a, b in zip(jax.tree.leaves(blocked["params"]),
                    jax.tree.leaves(whole["params"])):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b)
