"""Toy sizes of the cells added after `benchtoy.py` was written (a file
later PRs may not edit): `benchtoy.toy` looks a cell's traffic cut up in
`benchtoy.CELLS`, so a new cell's cut is registered here, before any
test runs. The configuration's cut is `benchtoy.TOY_WIDTHS` for every
cell; a runner reads what of it it knows."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchtoy  # noqa: E402

benchtoy.CELLS.setdefault('trinity-mini.mixed-closed64', dict(
    # a toy step of this model is ~100 ms on a loaded CPU and the tests'
    # window 0.6 s: answers of 2-4 tokens, so that every step completes
    # a request and the window sends some
    clients=4, prompt_tokens=[8, 24], output_tokens=[2, 4], grid=16,
    warm_completions=4, trace_steps=5,
    engine=dict(page_size=8, max_batch_size=4, prefill_chunk=16,
                num_pages=64, max_pages_per_seq=8, fused_k=1, spec_k=0)))
