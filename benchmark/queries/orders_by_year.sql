select o_orderstatus, count(*), sum(o_totalprice)
from orders
where o_orderdate >= date '{d0}' and o_orderdate < date '{d1}'
group by o_orderstatus order by o_orderstatus
