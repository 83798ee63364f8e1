"""Toy sizes for the benchmark's CPU tests: the committed configuration
and traffic files with their sizes cut, so that every key the runners
read is the real file's. Nothing timed here is a device number."""
import json
import os

from benchmarks import common

HERE = os.path.dirname(os.path.abspath(__file__))
TOY_WIDTHS = dict(num_layers=2, hidden_size=64, num_heads=4, head_dim=16,
                  ffn_hidden_size=256, vocab_size=512, max_seq_len=128)
CELLS = {
    # cell -> what to change in its traffic file
    'gpt3-1.3b.pretrain-2k': dict(
        seq_len=64, microbatch=2, accumulate_steps=2, distinct_batches=3,
        warm_steps=2, trace_steps=3),
    'bert-large.pretrain-512': dict(
        seq_len=64, batch=8, reference_sequences=4, flash_min_seq=64,
        distinct_batches=3, warm_steps=2, trace_steps=3),
    'gpt3-1.3b.chat-closed64': dict(
        clients=4, prompt_tokens=[8, 48], output_tokens=[4, 16], grid=16,
        warm_completions=4, trace_steps=5,
        engine=dict(page_size=8, max_batch_size=4, prefill_chunk=16,
                    num_pages=64, max_pages_per_seq=8, fused_k=1,
                    spec_k=0)),
}


def toy(manifest, cell_name):
    """(cell, config, traffic, runner module) at toy size."""
    cell = manifest.cell(cell_name)
    config = dict(manifest.config(cell), **TOY_WIDTHS)
    traffic = dict(manifest.traffic(cell), **CELLS[cell_name])
    runner = manifest.load_module('runners',
                                  config['runners'][traffic['kind']])
    return cell, config, traffic, runner


def recorded_trace():
    """The planes of trace_fixture.json: a cut of a real chip trace."""
    with open(os.path.join(HERE, 'trace_fixture.json')) as f:
        return json.load(f)['planes']


def manifest():
    return common.Manifest()
