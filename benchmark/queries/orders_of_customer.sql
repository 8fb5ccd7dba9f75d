select o_orderstatus, count(*), sum(o_totalprice)
from orders
where o_custkey = {k}
group by o_orderstatus order by o_orderstatus
