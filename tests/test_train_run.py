"""The trainer loop behind ``python -m repro.launch.train`` and
``chip_smoke.py``: :func:`repro.launch.train.run` on a reduced model.

Both step programs donate params and optimizer state, so the loop must
hand every step buffers it owns: a fresh optimizer state may not alias one
buffer twice, and the overhead probe copies what each timed call consumes.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.train import build_parser, run


def _settings(*extra):
    args = build_parser().parse_args(
        ["--steps", "5", "--batch", "2", "--seq", "16", *extra])
    cfg = dataclasses.replace(get_config("llama3_2_1b").reduced(),
                              n_layers=1)
    return cfg, args


def test_init_state_owns_every_buffer():
    from repro.core.ngd import NGDConfig, SPNGD
    from repro.models.transformer import DecoderLM
    cfg, _ = _settings()
    model = DecoderLM(cfg)
    opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                model.site_counts,
                NGDConfig(double_buffer=True, refresh_chunks=2))
    params = model.init(jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(opt.init(params))
    ptrs = [x.unsafe_buffer_pointer() for x in leaves]
    assert len(set(ptrs)) == len(ptrs)


@pytest.mark.parametrize("chunks,programs", [
    # refresh every step: Algorithm 2 sees the small batch's sampling noise
    ("1", ["train_step"] * 5),
    # the pipeline floors the interval at K + 1 = 3 steps
    ("2", ["train_step", "fast_step", "fast_step", "train_step",
           "fast_step"]),
])
def test_run_reports_each_step(chunks, programs):
    cfg, args = _settings("--refresh-chunks", chunks)
    recs = []
    out = run(cfg, args, label="test", on_step=recs.append)
    assert [r["step"] for r in recs] == [1, 2, 3, 4, 5]
    assert [r["program"] for r in recs] == programs
    for r in recs:
        assert all(np.isfinite(r[k])
                   for k in ("loss", "grad_norm", "update_norm", "dt"))
    assert set(out["programs"]) == {"train_step", "fast_step"}
    # the embedding is preconditioned at the batch's 2 x 16 token rows
    assert out["precond_rows"] == {"embed": (32, cfg.vocab)}
    # the returned state is live (donation consumed only the old buffers)
    assert all(not x.is_deleted() for x in jax.tree.leaves(out["state"]))


def test_run_overhead_probe_survives_donation(tmp_path):
    # a metrics run times both step programs on the same inputs again and
    # again: each timed call must get its own copy of what it donates
    path = tmp_path / "m.jsonl"
    cfg, args = _settings("--steps", "2", "--metrics-jsonl", str(path))
    run(cfg, args, label="test")
    events = [json.loads(line) for line in path.read_text().splitlines()]
    probe, = [e for e in events if e["type"] == "probe"]
    assert probe["fast_us"] > 0 and probe["refresh_us"] > 0
    assert sum(e["type"] == "step" for e in events) == 2
    rows = {e["program"]: e["families"] for e in events
            if e["type"] == "precond_rows"}
    assert rows == {p: {"embed": {"rows": 32, "d_in": cfg.vocab,
                                  "share": 32 / cfg.vocab}}
                    for p in ("train_step", "fast_step")}
