"""The short causal convolution and its SiLU (parallel/ssm.py
``causal_conv1d_silu``, called by the gated delta rule's mixer in
models/deltanet.py and the state-space mixer in models/hybrid.py), whether
the fused kernels engaged: of the elements (layers x sequences x positions
x channels) that go through the operation in the step most recently traced
(gauge ``tm_conv_elements_per_step``, set from static shapes while the step
is traced), the share whose shapes take the two Pallas kernels of
ops/conv_kernel.py (whole tiles of positions, whole lanes, 2,048 channels
or more) where the step runs on a TPU (gauge
``tm_conv_kernel_elements_per_step``), and not XLA's pad, four shifted
slices and SiLU differentiated by jax. 100 % in
``qwen3-next-80b-a3b.stream.x1`` (8,192 channels); 0 % in
``falcon-h1-34b.stream.x1``, whose 1,024 channels keep XLA's expressions
because its step was measured slower with the kernels in it, and which is
thereby known to bypass them; None where the program has no such gauge (a
model without the convolution, or the parent of the PR that added the
operation)."""

from benchmark import scopes


def read(run):
    elements = scopes.counter("tm_conv_elements_per_step")
    taken = scopes.counter("tm_conv_kernel_elements_per_step")
    if not elements or taken is None:
        return None
    return 100.0 * taken / elements
