"""Device time of the `lsh_hash` kernel in one whole streamed fit, in ms:
hashing the points chunk by chunk at the store build (`lsh_hash_pallas`)
and the support rows of every seed lane at every outer iteration, which
the compiler names after the vmapped jit they sit in
(`vmap_jit_lsh_hash_pallas_`)."""

import xtrace

KERNEL = r"^(vmap_jit_)?lsh_hash_pallas"


def read(run):
    ns, launches = xtrace.kernel_ns(run.summary, KERNEL)
    if launches == 0:
        return None
    return ns / 1e6 / run.counters["fits"]
