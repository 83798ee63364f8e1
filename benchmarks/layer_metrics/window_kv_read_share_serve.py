"""window_kv_read_share.serve — layer: serving engine. From the serving
ledger's counters (`roofline()`): KV tokens the window layers' attention
read in decode rows (`kv_read_tokens_window`: no more than the window a
row) over what the same layers would read without the bound
(`kv_read_tokens_full`), in percent."""


def read(trace, facts):
    kv = facts.get('kv_window') or {}
    if not kv.get('kv_read_tokens_full'):
        return None
    return 100.0 * kv['kv_read_tokens_window'] / kv['kv_read_tokens_full']
