ALIAS_OF = "peer_verdict_stall_share"
