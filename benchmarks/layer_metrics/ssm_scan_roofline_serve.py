"""ssm_scan_roofline.serve — layer: Pallas kernels. The least time the
chip could take for the traced selective-scan calls
(`benchmarks/ssm_bytes.py`: every (row, layer) update's state in and
out — the engine's `ssm_rows_total` over the traced steps x 655,360 B —
plus x, dt, y, B and C of its `ssm_tokens_total` positions, over the
HBM peak) over the device time of `pallas:selective_scan` in the trace,
in percent; 0 where the trace holds no such call. The counters and the
trace cover the same engine steps."""
from benchmarks import ssm_bytes
from benchmarks.common import log


def read(trace, facts):
    ssm = facts.get('ssm')
    chips = list((trace.get('chips') or {}).values())
    if not ssm or not chips:
        return None
    seconds = sum(v for c in chips for k, v in c['ops'].items()
                  if k.startswith('pallas:selective_scan')) / len(chips)
    rows = ssm['traced']['ssm_rows_total']
    if not seconds or not rows:
        return 0.0          # the kernel did not run in the traced steps
    least = ssm_bytes.least_seconds(
        rows, ssm['traced']['ssm_tokens_total'], ssm['state_row_bytes'],
        ssm['token_bytes'], facts['device_kind'])
    log(f'selective scan: {rows} (row, layer) updates over '
        f'{ssm["traced"]["ssm_tokens_total"]} positions, least '
        f'{least * 1e3:.2f} ms (hbm-bound) of {seconds * 1e3:.2f} ms')
    return 100.0 * least / seconds
