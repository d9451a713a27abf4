ALIAS_OF = "host_cpu_s_per_gib"
