"""paged_attention_chunk_ms_per_step.serve — layer: Pallas kernels.
Device time of the paged attention kernel's calls for groups whose rows
carry more than one query — the mixed step's prompt chunks: the classes
`pallas:paged_attention*` that END in `_chunk` (since PR 36 the kernel
appends it to the name of such a call, whichever body it runs) — per
traced engine step, mean over the chips. The rest of
`pallas:paged_attention*` is the decode rows' calls. 0.0 where the trace
holds no such class (a program that does not name them apart), as the
other kernel readers give; None in an untraced run."""


def read(trace, facts):
    chips = list((trace.get('chips') or {}).values())
    if not chips or not facts.get('traced_steps'):
        return None
    seconds = sum(v for c in chips for k, v in c['ops'].items()
                  if k.startswith('pallas:paged_attention')
                  and k.endswith('_chunk')) / len(chips)
    return seconds / facts['traced_steps'] * 1e3
