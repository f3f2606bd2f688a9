from .vision import (alexnet_cifar10, alexnet_cifar10_full, alexnet_imagenet,
                     lenet_mnist, mlp_mnist)
from .transformer import hybrid_lm, synthetic_token_batches, transformer_lm
from .generate import beam_search, generate, forward_cached, init_cache
from . import rbm
