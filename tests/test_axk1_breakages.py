"""tools/axk1_breakages.py's variants at toy size on the CPU: each patch
is reached through the engine's own route (prefix cache on, hits and
misses) and moves the served tokens off the reference (in float32 the
served path sits on it) — the one that only rounds too, at this toy's
large weights —, so a variant that silently patched nothing would be
seen here, not on the chip."""
import contextlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'tools'))

import axk1_breakages as tool  # noqa: E402
from test_axk1 import VOCAB, build  # noqa: E402
from test_serving_axk1 import reference, runner, serve  # noqa: E402

VARIANTS = ['served', 'no_k_pe', 'no_latent_norm', 'no_yarn_scale',
            'values_all_lanes', 'no_group_limit', 'no_shared', 'scores_bf16',
            'hit_other_doc', 'weights_f8', 'latents_f8']


@pytest.fixture(scope='module')
def model():
    """Weights ten times the initialiser's: scores far enough from 0
    that the softmax is not flat, and experts that outweigh the
    residual — else no piece of the attention or the router moves an
    argmax over 96 logits."""
    return build(initializer_range=0.2)


def test_the_tool_knows_these_variants():
    assert list(tool.variants()) == VARIANTS
    assert set(tool.NOT_HELD) <= set(VARIANTS)


@pytest.mark.parametrize('name', VARIANTS)
def test_a_variant_moves_the_served_path_off_the_reference(name, model):
    import jax
    rng = np.random.default_rng(4)
    docs = [rng.integers(1, VOCAB, n).tolist() for n in (16, 24)]
    prompts = [docs[i % 2] + rng.integers(1, VOCAB, 4 + i).tolist()
               for i in range(5)]
    calls = []
    jax.clear_caches()          # a patch inside a jitted call: fresh trace
    with contextlib.ExitStack() as stack:
        for obj, attr, value in tool.variants()[name]:
            if isinstance(value, property):
                calls.append(1)
            else:
                def counting(*a, _value=value, **k):
                    calls.append(1)
                    return _value(*a, **k)
                value = counting
            stack.enter_context(tool.patched(obj, attr, value))
        reqs, stats, _ = serve(model, prompts, (6,) * 5)
    jax.clear_caches()
    assert calls or name == 'served'
    assert stats['prefix_hit_tokens_total'] > 0
    params, layer, cfg = runner.reference_view(model)
    worst = 0.0
    for r in reqs:
        out, n = r.output_ids(), len(r.prompt)
        worst = max(worst, reference.token_gaps(
            params, layer, cfg, np.asarray(out, np.int32),
            np.arange(n - 1, len(out) - 1), out[n:]).max())
    if name == 'served':
        assert worst < 1e-4
    else:
        assert worst > 1e-3, f'{name} left the tokens on the reference'
