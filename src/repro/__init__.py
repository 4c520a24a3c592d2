"""repro: a multi-pod JAX framework reproducing PORTER (Li & Chi, 2023) --
decentralized nonconvex optimization with gradient clipping and
communication compression -- and extending it to a production-style
decentralized training stack (model zoo, mesh launcher, Pallas kernels,
roofline tooling).  See DESIGN.md for the system inventory.

Module map:

    api         THE entry point: ExperimentSpec (declarative experiment)
                + build(spec, loss_fn) -> Algorithm over the registry of
                all eight optimizers (porter-gc/dp, beer, porter-adam,
                dsgd, choco, dp-sgd, soteriafl); owns topology/compressor/
                engine construction and the gamma derivation
    core        the paper's algorithms and their substrate
      .comm_round   the one fused EF/gossip round primitive: CommRound
                    compresses an increment, accumulates surrogate q and
                    mixing mirror m, and applies a caller-supplied fused
                    update (ef_track/ef_step/ef_gossip kernels over the
                    flat tile layout); PORTER, PORTER-Adam, CHOCO-SGD and
                    SoteriaFL are thin clients of it
      .registry     the Algorithm protocol + registry repro.api publishes
                    every optimizer through
      .porter       Algorithm 1 (PORTER-DP / PORTER-GC / BEER)
      .baselines    DSGD, CHOCO-SGD, DP-SGD, SoteriaFL-SGD
      .gossip       dense / ring / packed wire executors + byte accounting
      .compression  rho-compressors (Definition 3)
      .clipping     smooth / piecewise clipping (Definition 2)
      .mixing       topologies and mixing matrices (Definition 1), plus
                    time-varying TopologySchedule generators (churn,
                    stragglers, graph rotation, ER resampling) with
                    window-connectivity validation and joint spectral gaps
      .privacy      LDP calibration and accounting (Theorem 1)
    kernels     Pallas TPU kernels (+ flatten: pytree <-> tile planes)
    launch      mesh builder, sharded step builders, train/serve drivers
    models, nn  the model zoo and its building blocks
    data        synthetic datasets matching the paper's experiments
    configs     per-architecture ModelConfigs (paper + production scale)
"""

__version__ = "0.1.0"
