"""A work counts module for the tests, named by a configuration's
``"work"`` key: every decode call of ``t`` tokens needs ``t`` GFLOP and
``t`` MB, every prefill 10 GFLOP a token and 10 MB."""


def decode_call(m, contexts):
    return 1e9 * len(contexts), 1e6 * len(contexts)


def prefill_call(m, prompt_len):
    return 1e10 * prompt_len, 1e7
