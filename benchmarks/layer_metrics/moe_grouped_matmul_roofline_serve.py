"""moe_grouped_matmul_roofline.serve — layer: Pallas kernels. The least
time the chip could take for the traced grouped-matmul calls
(`benchmarks/moe_bytes.py`: the expert weights they had to read — the
engine's `ptpu_moe_experts_touched_total` over the traced steps x one
expert's bytes — over the HBM peak, or their operations over the bf16
peak if that is longer) over the device time of
`pallas:moe_grouped_matmul` in the trace, in percent; 0 where the trace
holds no such call. The counter and the trace cover the same engine
steps."""
from benchmarks import moe_bytes
from benchmarks.common import log


def read(trace, facts):
    moe = facts.get('moe')
    chips = list((trace.get('chips') or {}).values())
    if not moe or not chips:
        return None
    seconds = sum(v for c in chips for k, v in c['ops'].items()
                  if k.startswith('pallas:moe_grouped_matmul')) / len(chips)
    touched = moe['traced']['moe_experts_touched_total']
    if not seconds or not touched:
        return 0.0          # the kernel did not run in the traced steps
    least, bound = moe_bytes.least_seconds(
        touched, moe['traced']['moe_rows_total'],
        moe['expert_weight_bytes'], facts['device_kind'])
    log(f'moe grouped matmul: {touched} experts touched and '
        f'{moe["traced"]["moe_rows_total"]} rows in '
        f'{moe["traced"]["moe_calls_total"]} traced calls, least '
        f'{least * 1e3:.2f} ms ({bound}-bound) of {seconds * 1e3:.2f} ms')
    return 100.0 * least / seconds
