"""Fluent edge configuration builders.

Reference parity: tez-runtime-library/.../library/conf/
{OrderedPartitionedKVEdgeConfig,UnorderedKVEdgeConfig,
UnorderedPartitionedKVEdgeConfig}.java — build EdgeProperty instances with
the runtime config serialized into the IO payloads (the "runtime config
travels inside the edge payload" rule, SURVEY.md §5.6).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from tez_tpu.common.payload import InputDescriptor, OutputDescriptor
from tez_tpu.dag.edge_property import (DataMovementType, DataSourceType,
                                       EdgeProperty, SchedulingType)


class _BaseEdgeConfigBuilder:
    _output_class: str = ""
    _input_class: str = ""
    _movement: DataMovementType = DataMovementType.SCATTER_GATHER

    def __init__(self, key_serde: str = "bytes", value_serde: str = "bytes"):
        self.conf: Dict[str, Any] = {
            "tez.runtime.key.class": key_serde,
            "tez.runtime.value.class": value_serde,
        }

    def set_conf(self, key: str, value: Any) -> "_BaseEdgeConfigBuilder":
        self.conf[key] = value
        return self

    def set_key_width(self, width: int) -> "_BaseEdgeConfigBuilder":
        """The edge's key lanes hold keys up to `width` bytes whole: what an
        ordered edge sorts by, a mesh edge ships, and an unordered edge's
        consumer may encode by (library/join.py)."""
        self.conf["tez.runtime.tpu.key.width.bytes"] = width
        return self

    def set_from_configuration(self, conf: Dict[str, Any]
                               ) -> "_BaseEdgeConfigBuilder":
        for k, v in conf.items():
            if k.startswith("tez.runtime."):
                self.conf[k] = v
        return self

    def build(self) -> "_EdgeConfig":
        return _EdgeConfig(self._output_class, self._input_class,
                           self._movement, dict(self.conf))


class _EdgeConfig:
    def __init__(self, output_class: str, input_class: str,
                 movement: DataMovementType, conf: Dict[str, Any]):
        self.output_class = output_class
        self.input_class = input_class
        self.movement = movement
        self.conf = conf

    def _descriptors(self) -> tuple:
        return (OutputDescriptor.create(self.output_class, payload=self.conf),
                InputDescriptor.create(self.input_class, payload=self.conf))

    def create_default_edge_property(self) -> EdgeProperty:
        out, inp = self._descriptors()
        return EdgeProperty.create(self.movement, DataSourceType.PERSISTED,
                                   SchedulingType.SEQUENTIAL, out, inp)

    def create_default_broadcast_edge_property(self) -> EdgeProperty:
        out, inp = self._descriptors()
        return EdgeProperty.create(DataMovementType.BROADCAST,
                                   DataSourceType.PERSISTED,
                                   SchedulingType.SEQUENTIAL, out, inp)

    def create_default_one_to_one_edge_property(self) -> EdgeProperty:
        out, inp = self._descriptors()
        return EdgeProperty.create(DataMovementType.ONE_TO_ONE,
                                   DataSourceType.PERSISTED,
                                   SchedulingType.SEQUENTIAL, out, inp)

    def create_default_custom_edge_property(self, edge_manager) -> EdgeProperty:
        out, inp = self._descriptors()
        return EdgeProperty.create_custom(edge_manager,
                                          DataSourceType.PERSISTED, out, inp)


class OrderedPartitionedKVEdgeConfig(_BaseEdgeConfigBuilder):
    """Sorted scatter-gather edge (DeviceSorter -> grouped merge input)."""
    _output_class = "tez_tpu.library.outputs:OrderedPartitionedKVOutput"
    _input_class = "tez_tpu.library.inputs:OrderedGroupedKVInput"
    _movement = DataMovementType.SCATTER_GATHER

    @staticmethod
    def new_builder(key_serde: str = "bytes", value_serde: str = "bytes"
                    ) -> "OrderedPartitionedKVEdgeConfig":
        return OrderedPartitionedKVEdgeConfig(key_serde, value_serde)

    def set_combiner(self, combiner: str) -> "OrderedPartitionedKVEdgeConfig":
        self.conf["tez.runtime.combiner.class"] = combiner
        return self

    def set_pipelined(self, enabled: bool = True
                      ) -> "OrderedPartitionedKVEdgeConfig":
        self.conf["tez.runtime.pipelined-shuffle.enabled"] = enabled
        return self

    def set_sort_mb(self, mb: int) -> "OrderedPartitionedKVEdgeConfig":
        self.conf["tez.runtime.io.sort.mb"] = mb
        return self


class UnorderedKVEdgeConfig(_BaseEdgeConfigBuilder):
    """Unsorted single-partition edge (broadcast / pass-through)."""
    _output_class = "tez_tpu.library.unordered:UnorderedKVOutput"
    _input_class = "tez_tpu.library.unordered:UnorderedKVInput"
    _movement = DataMovementType.BROADCAST

    @staticmethod
    def new_builder(key_serde: str = "bytes", value_serde: str = "bytes"
                    ) -> "UnorderedKVEdgeConfig":
        return UnorderedKVEdgeConfig(key_serde, value_serde)


class UnorderedPartitionedKVEdgeConfig(_BaseEdgeConfigBuilder):
    """Hash-partitioned unsorted scatter-gather edge."""
    _output_class = "tez_tpu.library.unordered:UnorderedPartitionedKVOutput"
    _input_class = "tez_tpu.library.unordered:UnorderedKVInput"
    _movement = DataMovementType.SCATTER_GATHER

    @staticmethod
    def new_builder(key_serde: str = "bytes", value_serde: str = "bytes"
                    ) -> "UnorderedPartitionedKVEdgeConfig":
        return UnorderedPartitionedKVEdgeConfig(key_serde, value_serde)

    def set_buffer_mb(self, mb: int) -> "UnorderedPartitionedKVEdgeConfig":
        self.conf["tez.runtime.unordered.output.buffer.size-mb"] = mb
        return self


class MeshOrderedPartitionedKVEdgeConfig(_BaseEdgeConfigBuilder):
    """Sorted scatter-gather edge over the ICI mesh exchange: producer sort,
    all-to-all transport and consumer merge are ONE SPMD program
    (library/mesh_io.py; reference roles: PipelinedSorter + ShuffleHandler +
    Fetcher + MergeManager).  Consumer parallelism must not exceed the mesh
    device count; keys/values are bounded by the configured widths."""
    _output_class = "tez_tpu.library.mesh_io:MeshOrderedPartitionedKVOutput"
    _input_class = "tez_tpu.library.mesh_io:MeshOrderedGroupedKVInput"
    _movement = DataMovementType.SCATTER_GATHER

    @staticmethod
    def new_builder(key_serde: str = "bytes", value_serde: str = "bytes"
                    ) -> "MeshOrderedPartitionedKVEdgeConfig":
        return MeshOrderedPartitionedKVEdgeConfig(key_serde, value_serde)

    def set_value_width(self, width: int
                        ) -> "MeshOrderedPartitionedKVEdgeConfig":
        self.conf["tez.runtime.tpu.mesh.value.width.bytes"] = width
        return self
