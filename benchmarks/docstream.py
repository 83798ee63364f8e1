"""Traffic of questions about shared long documents, beside
`loadgen.py` (whose closed loop drives it as it is): a fixed set of
documents, and an endless stream of requests each of which is one
document followed by a fresh question.

As in `loadgen.py`, every seed gets the SAME lengths in the SAME order:
the documents' lengths are the mid-quantiles of the log-uniform law the
traffic file states, rounded to whole pages (a document then ends on a
page boundary, so a later request maps every page of it); the order in
which documents are asked about, and the question and answer lengths,
walk permutations drawn from the file's own `order_seed`, a document
once a round, so each is asked about equally often. `--seed` draws the
token ids alone (and, in the runner, the weights).
"""
import numpy as np

from benchmarks.loadgen import log_uniform_grid


def document_lengths(traffic):
    """The documents' lengths, shortest first: whole pages."""
    page = traffic['engine']['page_size']
    grid = log_uniform_grid(*traffic['document_tokens'],
                            traffic['documents'])
    return [int(n) for n in np.rint(grid / page).astype(int) * page]


def documents(traffic, vocab, seed):
    """The documents' ids from `seed`, shortest first, drawn from
    [1, vocab)."""
    content = np.random.default_rng([int(seed), 1])
    return [content.integers(1, vocab, n).tolist()
            for n in document_lengths(traffic)]


def request_stream(traffic, docs, vocab, seed):
    """Endless (prompt_ids, max_new_tokens, document index): a
    document, a fresh question of ids from [1, vocab), and the length
    of the answer. Documents are asked about a round at a time, each
    round a new permutation; question and answer lengths each walk a
    permuted copy of their grid."""
    order = np.random.default_rng(traffic['order_seed'])
    content = np.random.default_rng([int(seed), 2])
    n = traffic['grid']
    questions = log_uniform_grid(*traffic['question_tokens'], n)
    outputs = log_uniform_grid(*traffic['output_tokens'], n)

    def rounds():
        while True:
            yield from order.permutation(len(docs))
    which = rounds()
    while True:
        for q, o in zip(order.permutation(questions),
                        order.permutation(outputs)):
            d = int(next(which))
            yield (docs[d] + content.integers(1, vocab, int(q)).tolist(),
                   int(o), d)
