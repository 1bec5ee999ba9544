"""Expert-FFN kernels' share of their roofline (``kernels/gmm``): the least
time the useful expert work of the window (2 x 3 x d x F per routed copy;
the weights of every slot that received a token plus each copy's
activation in and out) takes at the chip's peaks, over the summed device
time of the expert-FFN kernels. Moves ``tokens_per_s``."""


def read(c):
    return c.expert_ffn_roofline()
