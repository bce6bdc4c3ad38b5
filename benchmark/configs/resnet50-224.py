"""resnet50-224: ResNet-50 v1.5 through the program's ``models.ResNet50``.

What the harness needs of a configuration: ``build(cfg) -> Built``.
"""

import math
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import flops, weights
from benchmark.configs import Built


def build(cfg):
    from torchmpi_tpu.models.resnet import BottleneckBlock, ResNet
    from torchmpi_tpu.models import make_stateful_loss_fn

    m = cfg["model"]
    dtype = jnp.dtype(cfg["compute_dtype"])
    image, classes = m["image_size"], m["num_classes"]
    model = ResNet(
        stage_sizes=list(m["stage_sizes"]), block=BottleneckBlock,
        num_filters=m["num_filters"], num_classes=classes, dtype=dtype,
    )
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, image, image, 3)), train=True
        )
    )

    def init_leaf(name, shape, key):
        leaf = name.rsplit("/", 1)[-1]
        if leaf == "kernel":
            fan_in = math.prod(shape[:-1])
            gain = 2.0 if len(shape) == 4 else 1.0
            return weights.normal(key, shape, math.sqrt(gain / fan_in))
        if name.endswith("BatchNorm_2/scale"):
            # each block's last norm starts at zero, so each block starts as
            # the identity: the program's own init and the large-batch
            # recipe's (Goyal et al. 2017, arXiv:1706.02677)
            return jnp.zeros(shape, jnp.float32)
        if leaf in ("scale", "var"):
            return jnp.ones(shape, jnp.float32)
        return jnp.zeros(shape, jnp.float32)  # bias, mean

    make_tree = weights.seeded_tree(shapes, init_leaf)

    def state_at(key):
        tree = make_tree(key)
        return tree["params"], tree["batch_stats"]

    def make_data(seed, n):
        """``n`` float32 NHWC images in [0, 1] and their labels: each class
        is a pattern of 8x8 blocks, each image its class's pattern plus its
        own noise, so rows differ and the loss can fall."""
        rng = np.random.default_rng([int(seed), 1])
        lo = 8
        px = -(-image // lo)
        if lo * px != image:
            raise ValueError(f"image size {image} is not a multiple of {lo}")
        protos = rng.random((classes, lo, lo, 3), dtype=np.float32)
        y = rng.integers(0, classes, size=n).astype(np.int32)
        # bytes are the cheap noise: a third of a second for 2048 images,
        # where float32 normals take eight
        noise = rng.integers(0, 256, size=(n, image, image, 3),
                             dtype=np.uint8)
        x = np.empty((n, image, image, 3), np.float32)

        def fill(i):
            rows = slice(i, i + 64)
            np.multiply(noise[rows], np.float32(0.5 / 255.0), out=x[rows])
            blocks = x[rows].reshape(-1, lo, px, lo, px, 3)
            blocks += 0.5 * protos[y[rows]][:, :, None, :, None, :]

        with ThreadPoolExecutor(4) as pool:
            list(pool.map(fill, range(0, n, 64)))
        return x, y

    opt = cfg["optimizer"]
    return Built(
        loss_fn=make_stateful_loss_fn(model),
        optimizer=optax.sgd(opt["learning_rate"], momentum=opt["momentum"]),
        state_at=state_at,
        make_data=make_data,
        # the momentum trace; after one step it is the first gradient
        first_moment=lambda opt_state: opt_state[0].trace,
        flops_per_sample=flops.train_flops(flops.resnet_forward_flops(
            image, m["stage_sizes"], classes, m["num_filters"]
        )),
        input_dtype=dtype,
        loss_must_fall=True,
    )
