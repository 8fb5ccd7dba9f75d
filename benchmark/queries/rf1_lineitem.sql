insert into lineitem
select l_orderkey + (select max(o_orderkey) from orders),
       l_partkey, l_suppkey, l_linenumber, l_quantity, l_extendedprice,
       l_discount, l_tax, l_returnflag, l_linestatus, l_shipdate,
       l_commitdate, l_receiptdate, l_shipinstruct, l_shipmode, l_comment
from lineitem
where l_orderkey between (select min(o_orderkey) from orders)
                     and (select min(o_orderkey) + 7499 from orders)
