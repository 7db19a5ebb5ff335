"""Operations one ResNet-18 (CIFAR variant) training sample needs, from
shapes. jax-free.

Counted: the multiply-adds of every convolution and of the classifier,
2 FLOPs each, forward + weight gradient + input gradient (the stem has no
input gradient: nothing upstream of the image is trained). A tap that
falls on the zero padding is not an operation the algorithm needs and is
not counted — which is also how XLA's cost analysis counts, so the
program's manifest ``step_cost`` is a cross-check (within 5 %, the rest
being batch-norm and elementwise work that is left out here).

The usual published figure counts padded taps too ("0.56 GMAC forward",
3.33 GFLOP a training sample): ``dense_flops_per_sample``. MFU is taken
on the smaller, needed count.
"""

from __future__ import annotations


def _taps(size: int, kernel: int, stride: int) -> tuple:
    """(output size, in-bounds taps summed over outputs) of one spatial
    dimension under XLA's SAME padding."""
    out = -(-size // stride)
    pad = max((out - 1) * stride + kernel - size, 0)
    lo = pad // 2
    valid = 0
    for o in range(out):
        for k in range(kernel):
            if 0 <= o * stride + k - lo < size:
                valid += 1
    return out, valid


def conv_layers(config: dict) -> list:
    """[(name, macs_needed, macs_dense, has_input_grad)] per image."""
    m = config["model"]
    size, cin = m["image"][0], m["image"][2]
    layers = []

    def conv(name, size, cin, cout, kernel, stride, dgrad=True):
        out, valid = _taps(size, kernel, stride)
        layers.append((name, valid * valid * cin * cout,
                       (out * kernel) ** 2 * cin * cout, dgrad))
        return out

    conv("stem", size, cin, m["stages"][0], 3, 1, dgrad=False)
    cin = m["stages"][0]
    for s, (planes, blocks) in enumerate(zip(m["stages"], m["blocks_per_stage"])):
        for b in range(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            name = f"stage{s + 1}_block{b}"
            out = conv(name + ".conv1", size, cin, planes, 3, stride)
            conv(name + ".conv2", out, planes, planes, 3, 1)
            if stride != 1 or cin != planes:
                conv(name + ".shortcut", size, cin, planes, 1, stride)
            size, cin = out, planes
    layers.append(("classifier", cin * m["num_classes"],
                   cin * m["num_classes"], True))
    return layers


def flops_per_sample(config: dict) -> float:
    total = 0
    for _, macs, _, dgrad in conv_layers(config):
        total += 2 * macs * (3 if dgrad else 2)
    return float(total)


def dense_flops_per_sample(config: dict) -> float:
    """3 x forward with padded taps counted: the figure papers quote."""
    return float(sum(6 * dense for _, _, dense, _ in conv_layers(config)))
