from repro.core.carbon.intensity import (CITrace, GridRegion, REGIONS,
                                         STATE_CARBON_INDEX, get_region,
                                         region_ci)
from repro.core.carbon.geo import geolocate, haversine_km, IPInfo
from repro.core.carbon.path import Hop, NetworkPath, discover_path, path_ci
from repro.core.carbon.energy import (HostPowerModel, HOST_PROFILES,
                                      host_profile_for_endpoint, hop_power_w)
from repro.core.carbon.field import CarbonField, default_field
from repro.core.carbon.score import (carbonscore, transfer_emissions_g,
                                     transfer_emissions_g_batch,
                                     transfer_emissions_g_reference,
                                     TransferLedger)
from repro.core.carbon.telemetry import (HostMetrics, NetworkMetrics,
                                         TransferMetrics, Pmeter)

__all__ = [
    "CITrace", "GridRegion", "REGIONS", "STATE_CARBON_INDEX", "get_region",
    "region_ci", "geolocate", "haversine_km", "IPInfo", "Hop", "NetworkPath",
    "discover_path", "path_ci", "HostPowerModel", "HOST_PROFILES",
    "host_profile_for_endpoint", "hop_power_w", "CarbonField", "default_field",
    "carbonscore",
    "transfer_emissions_g", "transfer_emissions_g_batch",
    "transfer_emissions_g_reference", "TransferLedger",
    "HostMetrics", "NetworkMetrics", "TransferMetrics", "Pmeter",
]
