"""prefix_hit_share.serve — layer: serving engine. From the engine's
counters over the window: the prompt tokens the prefix cache mapped
(`prefix_hit_tokens_total`) over the tokens of every prompt it looked up
(`prompt_tokens_total`), in percent. Under 95 in the documents cell
means a document was evicted between two asks. None where the program
counts no prompt tokens or looked no prompt up."""


def read(trace, facts):
    c = facts.get('counters') or {}
    if not c.get('prompt_tokens_total'):
        return None
    return 100.0 * c['prefix_hit_tokens_total'] / c['prompt_tokens_total']
