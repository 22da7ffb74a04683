"""Device time per step under the scopes ``mla_q`` (the query's down- and
up-projection, the norm between them, the rotation of a head's last
channels) and ``mla_kv`` (the key and value's two projections, the norm,
the shared key's rotation and the key's assembly over the heads) on the
first chip, forward and backward, every latent mixer: what latent
attention spends around its kernels and its output projection."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "mla_q", "mla_kv")
