"""telemetry_ms_per_step.serve — layer: serving engine. The median
`serve::telemetry` span: what a step spends on observation (timeline,
ledger, gap monitor, metrics publish; ROADMAP D5)."""
from benchmarks.layer_metrics import _program_spans


def read(trace, facts):
    return _program_spans.median_ms(facts, 'serve::step',
                                    'serve::telemetry')
