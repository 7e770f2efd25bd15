from .mesh import (
    batch_axes, create_mesh, data_sharding, get_global_mesh, mesh_process_count,
    nonmodel_batch_axes, peek_global_mesh, place_global,
    replicate_sharding, resolve_elastic_axes, set_global_mesh, shard_batch,
    use_virtual_cpu_devices,
)
from .distributed import (
    all_hosts_flag, barrier_timeout_s, coordination_client, init_distributed_device,
    is_distributed_env, is_primary, reduce_tensor, world_info,
)
from .sharding import (
    PartitionRule, abstract_init_sharded, activation_bytes_per_device, build_opt_shardings,
    build_param_shardings, create_sharded_model, default_partition_rules, fsdp_size,
    build_quant_shardings, inherit_param_specs, match_rule, param_bytes_per_device,
    path_specs, quant_path_specs, quant_scale_spec, replicated_like,
    shard_pytree, spec_for_param, tp_size,
)
from .constraints import shard_activation
