from repro.configs.base import (REGISTRY, ArchConfig, get, load_all, reduced,
                                register)
