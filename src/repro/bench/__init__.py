# The Mirovia/Altis benchmark suite. Importing this package registers every
# benchmark with repro.core.registry (Table I). Levels:
#   0 — device microbenchmarks (BusSpeed*, DeviceMemory, MaxFlops)
#   1 — basic parallel algorithms (GUPS, BFS, GEMM, Pathfinder, Sort)
#   2 — application kernels (CFD, DWT2D, KMeans, LavaMD, Mandelbrot, NW,
#       ParticleFilter, SRAD, Where) + the DNN section (Activation, Pooling,
#       Batchnorm, Connected, Convolution, Dropout, RNN, Softmax, LRN) + a
#       language-model prefill (lm_prefill).

from repro.bench.level0 import devicemem, hostbus, maxflops  # noqa: F401
from repro.bench.level1 import bfs, gemm, gups, pathfinder, sort  # noqa: F401
from repro.bench.level2 import (  # noqa: F401
    cfd,
    dwt2d,
    kmeans,
    lavamd,
    mandelbrot,
    nw,
    particlefilter,
    srad,
    where,
)
from repro.bench.dnn import (  # noqa: F401
    activation,
    batchnorm,
    connected,
    convolution,
    dropout,
    lm_prefill,
    lrn,
    pooling,
    rnn,
    softmax,
)
