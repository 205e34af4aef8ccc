"""KG retrieval substrate: triple store, SubgraphRAG-style scorer,
neighbor sampler, the synthetic Freebase-like KGQA benchmark, and the
store held on the device (`store.DeviceKG`)."""
