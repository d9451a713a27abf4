ALIAS_OF = "device_idle_share"
