"""The program's Inception-ResNet-v2 graph for a configuration file."""


def graph(cfg: dict):
    """``zoo.inception_resnet_v2`` at the configuration's resolution, in
    float32. A ``repeats`` key, where the file has one, gives the block
    counts of a small copy; without it the published 10, 20, 10
    stand."""
    from repro.core import zoo
    if int(cfg["classes"]) != 1000:
        raise ValueError("the program's Inception-ResNet-v2 head has 1000 "
                         "classes")
    if cfg["dtype"] != "f32":
        raise ValueError("the program's Inception-ResNet-v2 runs in f32")
    extra = {"repeats": tuple(cfg["repeats"])} if "repeats" in cfg else {}
    return zoo.inception_resnet_v2(int(cfg["resolution"]), 4, **extra)
