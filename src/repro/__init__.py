"""ClusterFusion reproduction package: a TPU decode-serving stack that
fuses each decode layer into cluster-level Pallas kernels (DESIGN.md)."""
