"""Roofline share (%) of the Pallas paged-decode attention kernel in the
traced interval: the K and V of every live cached token, the query and
the output, over the kernel's device time."""
from bench import trace_reduce as trd
from bench.stats import decode_contexts, kernel_share

KERNEL = "paged_decode_attention"    # as the compiled program names it


def read(run):
    m = run["model"]

    def work(t0, t1):
        return trd.paged_attention_work(
            decode_contexts(run["all_requests"], t0, t1),
            n_layers=m["num_hidden_layers"],
            n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"])
    return kernel_share(run, KERNEL, work)
