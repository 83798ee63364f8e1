"""tools/phi4flash_breakages.py's variants at toy size on the CPU: each
patch is reached through the engine's own route and — but for the two
that only round (at 40 tokens over 97 logits a rounding flips no
argmax) — moves the served tokens off the reference (in float32 the
served path sits on it), so a variant that silently patched nothing
would be seen here, not on the chip."""
import contextlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'tools'))

import phi4flash_breakages as tool  # noqa: E402
from test_serving_phi4flash import (VOCAB, model, reference,  # noqa: E402,F401
                                    runner, serve)

VARIANTS = ['served', 'state_zeroed', 'state_bf16', 'no_window',
            'lambda_zero', 'cross_zero_plane', 'memory_gated',
            'fp8_activations']


def test_the_tool_knows_these_variants():
    assert list(tool.variants()) == VARIANTS


@pytest.mark.parametrize('name', VARIANTS)
def test_a_variant_moves_the_served_path_off_the_reference(name, model):
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (19, 7, 14)]
    calls = []
    with contextlib.ExitStack() as stack:
        for obj, attr, value in tool.variants()[name]:
            def counting(*a, _value=value, **k):
                calls.append(1)
                return _value(*a, **k)
            stack.enter_context(tool.patched(obj, attr, counting))
        reqs, _, _ = serve(model, prompts, (10, 10, 10))
    assert calls or name == 'served'
    params, layer, cfg = runner.reference_view(model)
    worst = 0.0
    for r in reqs:
        out, n = r.output_ids(), len(r.prompt)
        worst = max(worst, reference.token_gaps(
            params, layer, cfg, np.asarray(out, np.int32),
            np.arange(n - 1, len(out) - 1), out[n:]).max())
    if name == 'served':
        assert worst < 1e-4
    elif name in ('state_bf16', 'fp8_activations'):
        assert worst < 0.2          # rounded, not broken
    else:
        assert worst > 1e-3, f'{name} left the tokens on the reference'
