"""Runner ``flax_dp``: a flax image model through the library's
data-parallel path — ``hvd.init`` -> ``hvd.mesh`` ->
``training.init_train_state`` -> ``training.make_train_step`` (the
``hvd.DistributedOptimizer`` step). Every default of the library stays:
no ``HOROVOD_*`` variable, no bucket cap, no compression, per-shard
batch-norm. Reads configs/resnet50.json's keys and an ``image_batches``
traffic file."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.resnet import BottleneckBlock, ResNet
from horovod_tpu.training import (
    init_train_state, make_train_step, replicate_state)

from benchmark import reference

# The measured step against the plain one-device step (reference.py), both
# in the configuration's mixed precision (bf16 operands, float32
# statistics, head and loss), so what differs is the order of roundings
# and, on four chips, of the float32 gradient sum.
#
# Loss: read 7e-8 to 2.2e-5 relative on the chip (PERF.md, PR 23).
LOSS_RTOL = 2e-4
# Updates, relative L2 per sampled leaf. At initialisation ResNet-50's
# backward pass is badly conditioned (likely batch-norm's backward, which
# subtracts means of nearly equal size). The program and the reference
# are the same function (1e-6 apart in float64, params' float32 casts
# included) and yet 0.6-0.9 % apart in float32 and 3-4 % in bf16 on the
# CPU; on the chip every leaf below the head read 4.0-7.9 %, the head
# itself 0.21-0.24 % (PR 23's chip runs). So this check cannot see a head or a
# loss in bf16. It is there for what moves an update by tens of percent
# or more: a gradient exchange that is missing, doubled or summed where
# it should average, batch-norm statistics taken over the wrong set, a
# wrong learning rate or momentum.
UPDATE_RTOL = 0.15
HEAD_UPDATE_RTOL = 2e-2
# Leaves that get a gradient at initialisation (each block's last norm
# starts at scale zero, so the convolutions inside a block start with
# none): the stem, which is at the far end of the whole backward pass;
# projections and last norms through the depth; the head.
SAMPLED_LEAVES = (
    ("conv_init", "kernel"),
    ("BottleneckBlock_0", "conv_proj", "kernel"),
    ("BottleneckBlock_0", "BatchNorm_2", "scale"),
    ("BottleneckBlock_{mid}", "BatchNorm_2", "scale"),
    ("BottleneckBlock_{last}", "BatchNorm_2", "scale"),
    ("Dense_0", "kernel"),
)


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


class Job:
    sample_unit = "images"

    def __init__(self, config, traffic, devices, seed):
        hvd.init(devices=devices)
        self.mesh, n = hvd.mesh(), hvd.size()
        self.replicas = n
        self.config = config
        self.dtype = jnp.dtype(config["dtype"])
        size, classes = config["image_size"], config["num_classes"]
        self.batch = traffic["batch_per_chip"] * n
        self.samples_per_step = self.batch
        self.model_flops_per_step = (
            self.batch * config["flops"]["train_flops_per_image"])
        blocks = sum(config["stage_sizes"])
        self.sampled = [tuple(k.format(mid=blocks // 2, last=blocks - 1)
                              for k in path) for path in SAMPLED_LEAVES]

        opt_cfg = config["optimizer"]
        if opt_cfg["name"] != "sgd":
            raise ValueError(f"flax_dp runner: optimizer {opt_cfg!r}")
        optimizer = optax.sgd(opt_cfg["learning_rate"],
                              momentum=opt_cfg["momentum"])
        model = ResNet(stage_sizes=tuple(config["stage_sizes"]),
                       block_cls=BottleneckBlock, num_classes=classes,
                       num_filters=config["num_filters"], dtype=self.dtype)
        k_params, k_images, k_labels = jax.random.split(
            jax.random.PRNGKey(seed), 3)
        sample = jnp.zeros((1, size, size, config["image_channels"]),
                           jnp.float32)
        # Weights and the batch are made on the device from the seed, each
        # in one jitted call.
        self.state = replicate_state(
            jax.jit(lambda k: init_train_state(model, optimizer, k, sample))(
                k_params), self.mesh)
        sharded = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
        shape = (self.batch, size, size, config["image_channels"])
        self.images, self.labels = jax.jit(
            lambda ki, kl: (
                jax.random.uniform(ki, shape, jnp.float32),
                jax.random.randint(kl, shape[:1], 0, classes, jnp.int32)),
            out_shardings=(sharded, sharded))(k_images, k_labels)
        self.step_fn = make_train_step(model, optimizer, self.mesh)
        self.compiled = None
        self._before = self._ref_loss = self._ref_after = None

    def lower(self):
        return self.step_fn.lower(self.state, self.images, self.labels)

    def step(self):
        self.state, loss = self.compiled(self.state, self.images,
                                         self.labels)
        return loss

    def _sample(self, params):
        return [np.asarray(_leaf(params, p), np.float32)
                for p in self.sampled]

    def prepare_reference(self):
        """Before the first step (which donates the state): the plain
        step from these weights on this batch, on the first device."""
        one = self.mesh.devices.flat[0]
        put = lambda x: jax.device_put(x, one)
        cfg, opt = self.config, self.config["optimizer"]
        ref = jax.jit(lambda p, x, y: reference.resnet50_sgd_step(
            p, x, y, self.replicas, tuple(cfg["stage_sizes"]), self.dtype,
            opt["learning_rate"], opt["momentum"]))
        self._before = self._sample(self.state.params)
        loss, after = ref(jax.tree_util.tree_map(put, self.state.params),
                          put(self.images), put(self.labels))
        self._ref_loss = float(loss)
        self._ref_after = self._sample(after)

    def compare_reference(self, first_loss):
        err = abs(first_loss - self._ref_loss) / abs(self._ref_loss)
        checks = [dict(what="first-step loss vs plain one-device step",
                       got=first_loss, want=self._ref_loss, rel_err=err,
                       tol=LOSS_RTOL,
                       ok=bool(np.isfinite(err) and err <= LOSS_RTOL))]
        after = self._sample(self.state.params)
        for path, p0, got, want in zip(self.sampled, self._before, after,
                                       self._ref_after):
            d_got, d_want = got - p0, want - p0
            norm = float(np.linalg.norm(d_want))
            err = float(np.linalg.norm(d_got - d_want)) / norm if norm else \
                float("inf")
            tol = HEAD_UPDATE_RTOL if path[0] == "Dense_0" else UPDATE_RTOL
            checks.append(dict(
                what="update of " + "/".join(path) + " after one step",
                update_norm=norm, rel_err=err, tol=tol,
                ok=bool(np.isfinite(err) and err <= tol)))
        return checks

    def close(self):
        hvd.shutdown()


def build(config, traffic, devices, seed):
    if traffic["kind"] != "image_batches":
        raise ValueError("the flax_dp runner takes image_batches traffic, "
                         f"not {traffic['kind']!r}")
    return Job(config, traffic, devices, seed)
