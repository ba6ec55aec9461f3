"""Work of the FedAvg MNIST CNN (McMahan et al., AISTATS 2017), from its
widths: conv 5x5x32 and 5x5x64 with SAME padding, each followed by 2x2
max pooling, FC 512, softmax 10, on 28x28 single-channel images."""


def params(model):
    """Parameter count (1,663,370 at the published widths)."""
    k, s = model["kernel"], model["image"]
    c1, c2, fc, out = model["conv1"], model["conv2"], model["fc"], model["classes"]
    flat = (s // 4) ** 2 * c2
    return (k * k * c1 + c1) + (k * k * c1 * c2 + c2) + (flat * fc + fc) + (fc * out + out)


def forward_flops(model):
    """FLOPs of one image's forward pass: 2 per multiply-add of the convolutions
    and dense layers (bias, ReLU, pooling and softmax left out)."""
    k, s = model["kernel"], model["image"]
    c1, c2, fc, out = model["conv1"], model["conv2"], model["fc"], model["classes"]
    macs = (s * s * c1 * k * k                    # conv1 at 28x28
            + (s // 2) ** 2 * c2 * k * k * c1     # conv2 at 14x14
            + (s // 4) ** 2 * c2 * fc             # FC 3136 -> 512
            + fc * out)                           # FC 512 -> 10
    return 2 * macs


def round_flops(cfg):
    """Model FLOPs of one FL round: forward and backward (3x forward) of
    every locally trained image, M clients x local steps x B, plus one
    forward of the proxy set for each of the M leave-one-out models."""
    rnd, f = cfg["round"], forward_flops(cfg["model"])
    m = rnd["n_sched"]
    return (3 * f * m * rnd["local_steps"] * rnd["batch_size"]
            + f * m * rnd["proxy_examples"])
